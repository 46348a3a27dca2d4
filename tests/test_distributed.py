"""Distributed machinery beyond sharding specs: compressed pod psum under
a real multi-pod mesh (subprocess, 8 virtual hosts) + hint no-op safety."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.train.compression import compressed_psum

    mesh = jax.make_mesh((2, 4), ("pod", "data"))
    # per-pod distinct gradient shards; compressed psum over 'pod'
    g = jnp.stack([jnp.linspace(-1, 1, 512), jnp.linspace(0, 2, 512)])

    fn = jax.shard_map(lambda t: compressed_psum(t[0], "pod"),
                       mesh=mesh, in_specs=(P("pod"),), out_specs=P(),
                       check_vma=False)
    out = fn(g.reshape(2, 1, 512))
    want = np.asarray(g).sum(0)
    err = np.abs(np.asarray(out) - want).max()
    assert err < 4 * (2.0 / 127), err   # block-quantization error bound
    print("COMPRESSED_PSUM_OK", err)

    # gpipe in the same process over the pod axis (2 stages)
    from repro.distributed.pipeline import gpipe, split_stages
    ws = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    def stage_fn(sws, h):
        def body(hh, w):
            return jnp.tanh(hh @ w), None
        out, _ = jax.lax.scan(body, h, sws)
        return out
    mesh2 = jax.make_mesh((2,), ("pod",))
    out = gpipe(stage_fn, split_stages(ws, 2), x, mesh=mesh2, axis="pod",
                n_micro=2)
    ref = x
    for i in range(4):
        ref = jnp.tanh(ref @ ws[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    print("PIPE_POD_OK")
""")


@pytest.mark.slow
def test_compressed_pod_psum_and_pipeline_multihost():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "COMPRESSED_PSUM_OK" in out.stdout
    assert "PIPE_POD_OK" in out.stdout


def test_hint_noop_without_mesh():
    from repro.distributed.hints import hint, hint_kv
    x = jnp.ones((4, 8))
    np.testing.assert_array_equal(np.asarray(hint(x, "data", None)),
                                  np.asarray(x))
    kv = jnp.ones((2, 16, 4, 8))
    np.testing.assert_array_equal(np.asarray(hint_kv(kv)), np.asarray(kv))


def test_fit_spec_never_violates_divisibility():
    from _hypothesis_compat import given, settings, st
    from repro.distributed import sharding as shd

    mesh = shd.abstract_mesh((2, 16, 16), ("pod", "data", "model"))

    @given(st.integers(1, 4096), st.sampled_from(
        [None, "model", ("pod", "data"), ("pod", "data", "model")]))
    @settings(max_examples=100, deadline=None)
    def inner(dim, want):
        got = shd._fit(mesh, dim, want)
        size = shd._axis_size(mesh, got)
        assert dim % size == 0

    inner()
