"""Query families: one module per family, named by a traffic mix's
``query``.  Each module's ``FAMILY`` is a :class:`bench.generator.Family`
that builds the mix's queues and judges their answers against
:mod:`bench.reference`."""
