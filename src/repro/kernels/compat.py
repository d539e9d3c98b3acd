"""The one import point for the Pallas/TPU surface and adjacent JAX APIs.

Kernel and parallelism code imports the Pallas TPU memory spaces, DMA
helpers, compiler params, ``shard_map`` and mesh construction only from
here, so an API move in a later JAX is a change to this file alone.  It
targets the installed JAX (0.9) and carries no fallbacks for older ones.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType

# ---------------------------------------------------------------------------
# Pallas TPU surface
# ---------------------------------------------------------------------------

# Memory spaces / DMA helpers — re-exported so kernel modules never touch
# pltpu attributes directly.
VMEM = pltpu.VMEM
SMEM = pltpu.SMEM
ANY = pl.ANY
SemaphoreType = pltpu.SemaphoreType
make_async_copy = pltpu.make_async_copy


def compiler_params(
    *, dimension_semantics: Optional[Sequence[str]] = None, **kwargs
):
    """``pltpu.CompilerParams`` with ``dimension_semantics`` as a tuple."""
    if dimension_semantics is not None:
        kwargs["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**kwargs)


def on_tpu() -> bool:
    """True when the default backend is a real TPU."""
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Pallas kernels run compiled on TPU, interpreted everywhere else."""
    return not on_tpu()


# ---------------------------------------------------------------------------
# shard_map / mesh
# ---------------------------------------------------------------------------


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with ``check_vma`` off by default: the k-NN wave
    step intentionally mixes replicated and sharded outputs."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with explicit-Auto axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(AxisType.Auto,) * len(tuple(axis_names)),
    )


def donation_enabled() -> bool:
    """True where jax buffer donation actually takes effect (TPU/GPU)."""
    return jax.default_backend() in ("tpu", "gpu")


def donating_jit(fun=None, *, static_argnames=(), donate_argnums=()):
    """``jax.jit`` that only donates where donation is implemented.

    Buffer donation is a no-op (plus a warning per compile) on CPU; dropping
    the donation there keeps logs clean and lets tests reuse inputs, while
    TPU/GPU get the in-place graph update the fused wave pipeline relies on.
    """
    if fun is None:
        return functools.partial(
            donating_jit,
            static_argnames=static_argnames,
            donate_argnums=donate_argnums,
        )

    # Resolved on first call, not at decoration time: deciding needs
    # ``jax.default_backend()``, and module import must never initialize
    # device state (the dry-run sets XLA_FLAGS after imports).
    cache: dict = {}

    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        if "jitted" not in cache:
            donate = donate_argnums if donation_enabled() else ()
            cache["jitted"] = jax.jit(
                fun, static_argnames=static_argnames, donate_argnums=donate
            )
        return cache["jitted"](*args, **kwargs)

    wrapper.clear_cache = cache.clear  # test hook
    return wrapper
