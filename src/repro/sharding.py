"""Mesh context + sharding-constraint helpers shared by models and launch.

``MeshCtx`` carries the axis names so model code never hard-codes a mesh
shape; on a single device (smoke tests) the context is ``None`` and every
helper becomes a no-op.

Divisibility fallback (DESIGN.md §4): a dim is only sharded if the axis size
divides it — otherwise that dim stays replicated and the event is recorded
in ``MeshCtx.fallbacks`` for the roofline report.
"""
from __future__ import annotations

import contextlib
import dataclasses
import inspect
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["MeshCtx", "current_mesh_ctx", "mesh_context", "shard", "axis_size",
           "DATA", "MODEL", "BOTH", "TRIAL_AXIS", "trial_devices",
           "trial_mesh", "shard_trials"]

DATA = "__data__"    # placeholder resolved to the ctx's (possibly stacked) data axes
MODEL = "__model__"  # placeholder resolved to the ctx's model axis
BOTH = "__both__"    # data axes + model axis (fully-sharded dim)

_state = threading.local()


@dataclasses.dataclass
class MeshCtx:
    mesh: Mesh
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: Optional[str] = "model"   # None = pure data parallelism
    fallbacks: list = dataclasses.field(default_factory=list)

    @property
    def data_size(self) -> int:
        out = 1
        for a in self.data_axes:
            out *= self.mesh.shape[a]
        return out

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis] if self.model_axis else 1

    def resolve(self, spec_entry):
        if spec_entry == DATA:
            return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]
        if spec_entry == MODEL:
            return self.model_axis
        if spec_entry == BOTH:
            if self.model_axis is None:
                return self.resolve(DATA)
            return tuple(self.data_axes) + (self.model_axis,)
        return spec_entry

    def spec(self, *entries) -> P:
        return P(*[self.resolve(e) for e in entries])


def current_mesh_ctx() -> Optional[MeshCtx]:
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def mesh_context(ctx: Optional[MeshCtx]):
    prev = getattr(_state, "ctx", None)
    _state.ctx = ctx
    try:
        yield ctx
    finally:
        _state.ctx = prev


def axis_size(entry) -> int:
    """Size of a placeholder axis under the current ctx (1 if no mesh)."""
    ctx = current_mesh_ctx()
    if ctx is None:
        return 1
    ax = ctx.resolve(entry)
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= ctx.mesh.shape[a]
        return n
    return ctx.mesh.shape[ax]


def shard(x: jax.Array, *entries, note: str = "") -> jax.Array:
    """Apply a sharding constraint with divisibility fallback. ``entries``
    use DATA/MODEL placeholders or literal axis names / None."""
    ctx = current_mesh_ctx()
    if ctx is None:
        return x
    resolved = []
    for dim, e in enumerate(entries):
        if e is None:
            resolved.append(None)
            continue
        ax = ctx.resolve(e)
        size = axis_size(e)
        if size <= 1:
            resolved.append(None)
        elif x.shape[dim] % size != 0:
            ctx.fallbacks.append((note or "tensor", dim, x.shape[dim], size))
            resolved.append(None)
        else:
            resolved.append(ax)
    sh = NamedSharding(ctx.mesh, P(*resolved))
    return jax.lax.with_sharding_constraint(x, sh)


# --------------------------------------------------------------------------
# trial-axis sharding (Monte-Carlo sweeps)
# --------------------------------------------------------------------------

TRIAL_AXIS = "trials"


def trial_devices(devices=None) -> Tuple[jax.Device, ...]:
    """Resolve the ``devices`` argument of ``sweep``/``sweep_rounds``.

    ``None`` means every local device; an int means the first that many
    local devices; a sequence of ``jax.Device`` is taken as-is."""
    if devices is None:
        return tuple(jax.devices())
    if isinstance(devices, int):
        ds = jax.devices()
        if not 1 <= devices <= len(ds):
            raise ValueError(f"devices must be in 1..{len(ds)} (local "
                             f"device count), got {devices}")
        return tuple(ds[:devices])
    ds = tuple(devices)
    if not ds:
        raise ValueError("devices must name at least one device")
    return ds


def trial_mesh(devices: Sequence[jax.Device]) -> Mesh:
    """1-D mesh over the Monte-Carlo trial axis."""
    return Mesh(np.asarray(devices, dtype=object), (TRIAL_AXIS,))


def shard_trials(fn, devices: Sequence[jax.Device], replicated: Tuple[int, ...] = ()):
    """Shard ``fn`` over a 1-D trial mesh: every argument and every output
    is split along its leading (chunk) axis across ``devices`` in contiguous
    blocks, each device runs ``fn`` on its block, and outputs come back
    concatenated in global chunk order.  ``fn`` must be collective-free —
    the Monte-Carlo scans qualify because trials are independent.

    ``replicated`` names positional argnums that every device sees whole
    (broadcast, not split): small runtime parameters like PRNG base keys,
    per-chunk offset vectors, and the bucketed evaluators' gather plans.

    Mechanism: ``jax.shard_map`` runs ``fn`` itself on each device's block
    of chunks, so every device compiles the single-device program at a
    smaller chunk count.  Pallas kernels inside ``fn`` (the greedy
    assignment) need this: XLA cannot partition a Mosaic call, so a
    GSPMD-partitioned ``jit`` of the same scan is refused on a TPU.

    Returns ``jax.jit`` of the ``shard_map``-ped ``fn`` (callers must NOT
    wrap it in another ``jax.jit``); ``fn``'s positional parameters are
    counted from its signature."""
    repl = frozenset(replicated)
    nargs = len(inspect.signature(fn).parameters)
    specs = tuple(P() if i in repl else P(TRIAL_AXIS) for i in range(nargs))
    # fn holds no collectives, so there is nothing for the varying-axes
    # check to protect; it would only ask every constant scan carry in
    # the engine to be marked varying
    return jax.jit(jax.shard_map(fn, mesh=trial_mesh(tuple(devices)),
                                 in_specs=specs, out_specs=P(TRIAL_AXIS),
                                 check_vma=False))
