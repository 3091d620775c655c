"""Public op: fused IVF cluster scan (kernel on TPU, jnp oracle elsewhere)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ivf_scan.ivf_scan import ivf_scan_pallas
from repro.kernels.ivf_scan.ref import ivf_scan_ref


def resolve_impl(impl: str) -> str:
    """What ``impl`` runs as on this backend: ``auto`` is the Pallas kernel
    on a TPU and the jnp oracle elsewhere."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


@functools.partial(jax.jit, static_argnames=("k", "impl"))
def ivf_scan(q_groups, group_cluster, slab, valid, k: int, *, impl: str = "auto"):
    """impl: auto | pallas | interpret | ref.  See ivf_scan.py for semantics."""
    impl = resolve_impl(impl)
    with jax.named_scope("ivf_scan"):
        if impl == "pallas":
            return ivf_scan_pallas(q_groups, group_cluster, slab, valid, k)
        if impl == "interpret":
            return ivf_scan_pallas(q_groups, group_cluster, slab, valid, k,
                                   interpret=True)
        return ivf_scan_ref(q_groups, group_cluster, slab, valid, k)
