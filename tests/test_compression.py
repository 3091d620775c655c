"""Gradient compression: quantisation fidelity, error feedback, sharded sum."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.training.compression import (
    ErrorFeedback,
    _dequantize_blocks,
    _quantize_blocks,
    dcn_bytes_saved,
    quantization_residual,
)


def test_block_quantization_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000) * 0.01, jnp.float32)
    q, s, pad = _quantize_blocks(x, 256)
    y = _dequantize_blocks(q, s, pad, x.shape)
    # per-block absmax scaling: error <= scale/2 = absmax/254
    err = np.abs(np.asarray(x - y))
    assert err.max() <= float(np.abs(np.asarray(x)).max()) / 127.0


def test_error_feedback_accumulates_to_truth():
    """With error feedback, the *sum* of sent gradients converges to the sum
    of true gradients (the EF guarantee)."""
    rng = np.random.default_rng(1)
    true = [jnp.asarray(rng.standard_normal(512) * 1e-3, jnp.float32)
            for _ in range(20)]
    ef = ErrorFeedback.init(true[0])
    sent_total = jnp.zeros_like(true[0])
    true_total = jnp.zeros_like(true[0])
    for g in true:
        send, ef = ErrorFeedback.apply(g, ef)
        sent_total = sent_total + send
        true_total = true_total + g
    resid = np.abs(np.asarray(sent_total - true_total))
    # leftover is at most one quantisation step
    assert resid.max() <= float(np.abs(np.asarray(true_total)).max()) / 64.0


def test_dcn_bytes_saved_reports_gain():
    r = dcn_bytes_saved(1_000_000_000, n_pods=2)
    assert r["saving"] > 1.5


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.training.compression import compressed_psum_leaf

mesh = jax.make_mesh((2,), ("pod",))
rng = np.random.default_rng(2)
x = jnp.asarray(rng.standard_normal((2, 515)) * 0.02, jnp.float32)

f = jax.shard_map(lambda v: compressed_psum_leaf(v[0], "pod"),
                  mesh=mesh, in_specs=(P("pod", None),), out_specs=P(None),
                  check_vma=False)
with mesh:
    got = f(x)
want = np.asarray(x).sum(0)
err = np.abs(np.asarray(got) - want).max()
tol = 2 * np.abs(np.asarray(x)).max() / 127.0
assert err <= tol, (err, tol)
print("OK")
"""


def test_compressed_psum_2pod_subprocess():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _SUBPROC, src],
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "XLA_FLAGS": ""})
    assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr
