"""Mesh-aware sharding helpers.

Model code calls :func:`constrain` with *intent* (which logical mesh axes a
dim belongs to); the helper silently drops axes that are absent from the
current mesh (e.g. ``"pod"`` on a single-pod mesh) or that are *manual* in the
enclosing ``shard_map`` (where GSPMD must not see them).  Outside any mesh the
helpers are no-ops, so the same model code runs in single-device smoke tests.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

DP_AXES: tuple[str, ...] = ("pod", "data")   # data-parallel axes (outer first)
TP_AXIS: str = "model"                        # tensor/expert-parallel axis

AxisEntry = Union[None, str, Sequence[str]]


def _auto_axes() -> set[str]:
    """Mesh axes GSPMD may shard over (present and not shard_map-manual)."""
    m = jax.sharding.get_abstract_mesh()
    return {n for n, t in zip(m.axis_names, m.axis_types)
            if t != AxisType.Manual}


def filter_spec(*entries: AxisEntry) -> Optional[P]:
    """Build a PartitionSpec keeping only currently-usable axes.

    Returns None when no axis survives (caller should skip the constraint).
    """
    usable = _auto_axes()
    if not usable:
        return None
    fixed: list[AxisEntry] = []
    nontrivial = False
    for e in entries:
        if e is None:
            fixed.append(None)
        elif isinstance(e, str):
            if e in usable:
                fixed.append(e)
                nontrivial = True
            else:
                fixed.append(None)
        else:
            kept = tuple(a for a in e if a in usable)
            if kept:
                fixed.append(kept if len(kept) > 1 else kept[0])
                nontrivial = True
            else:
                fixed.append(None)
    if not nontrivial:
        return None
    return P(*fixed)


def constrain(x: jax.Array, *entries: AxisEntry) -> jax.Array:
    """`with_sharding_constraint` over the axes that exist right now.

    ``constrain(x, DP_AXES, None, TP_AXIS)`` shards dim0 over ("pod","data")
    and dim2 over "model" — on whatever subset of those axes exists and is
    GSPMD-visible right now.
    """
    spec = filter_spec(*entries)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def per_shard(fn, in_specs: tuple, out_specs):
    """`fn` made manual over every mesh axis GSPMD could still partition.

    For Pallas kernel calls: Mosaic kernels cannot be auto-partitioned, so
    inside a shard_map that leaves an axis auto (the train step is manual
    over the DP axes only) or under a multi-device mesh, the kernel runs
    per shard.  Specs are tuples of axis intents as for :func:`constrain`,
    one per argument (a tuple of them for several outputs); outside any
    mesh `fn` comes back unchanged.
    """
    axes = _auto_axes()
    if not axes:
        return fn

    def spec(entries):
        return filter_spec(*entries) or P()

    outs = (tuple(spec(e) for e in out_specs)
            if isinstance(out_specs[0], tuple) else spec(out_specs))
    # every axis, the enclosing shard_map's manual ones too: a nested
    # shard_map over the auto axes alone lowers with only those manual,
    # which Mosaic refuses
    names = set(jax.sharding.get_abstract_mesh().axis_names)
    return jax.shard_map(fn, in_specs=tuple(spec(e) for e in in_specs),
                         out_specs=outs, axis_names=names, check_vma=False)


def manual_axes_present(*names: str) -> tuple[str, ...]:
    """Which of `names` are *manual* axes right now (i.e. usable by explicit
    collectives like psum/ppermute). Inside shard_map, only the axes in
    `axis_names` qualify; auto axes would raise 'unbound axis name'."""
    m = jax.sharding.get_abstract_mesh()
    manual = {n for n, t in zip(m.axis_names, m.axis_types)
              if t == AxisType.Manual}
    return tuple(n for n in names if n in manual)


def axis_size(name: str) -> int:
    m = jax.sharding.get_abstract_mesh()
    return int(m.shape[name]) if name in m.axis_names else 1
