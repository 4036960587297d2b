"""Train/serve step builders.

Architecture (see DESIGN.md §2/§3):
  * one jit per step; inside it a shard_map that is MANUAL over the DP axes
    ("pod","data") and AUTO over "model" (GSPMD handles tensor parallelism
    from sharding constraints).
  * ZeRO-3 layout (default with the hierarchical comm mode): params +
    optimizer state are stored scattered over "data"; layer weights are
    all-gathered at use inside the layer scan (the model's `gather` hook),
    so autodiff emits the in-pod reduce-scatter of gradients for free.
  * the cross-pod ("WAN") stage is the explicit MPWide WidePath:
    streamed/chunked/paced/compressed psum over the "pod" axis.
"""
from __future__ import annotations

import functools
import inspect
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import RunConfig
from repro.core import buckets as bk
from repro.core.autotune import autotune_path
from repro.core.collectives import (flat_allreduce, gateway_allreduce,
                                    streamed_psum)
from repro.core.overlap import accum_grads, flush_hook
from repro.core.path import INTERPOD, WidePath
from repro.launch.roofline import modeled_compute_window
from repro.models import build_model
from repro.models.param import (PD, is_pd_leaf, leaf_bytes_pd, tree_abstract,
                                tree_fsdp_dims, tree_init, tree_specs)
from repro.optim import adamw_update, init_opt_state, lr_at

NOFSDP = -1


def dp_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _manual_part(spec: P, manual: set[str]) -> P:
    """Keep only manual axes of a spec (shard_map in_specs see manual axes)."""
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, str):
            out.append(e if e in manual else None)
        else:
            kept = tuple(a for a in e if a in manual)
            out.append(kept[0] if len(kept) == 1 else (kept or None))
    return P(*out)


def _strip_layer_dim(dims_tree):
    """Scan strips the leading layer dim: shift gather dims down by one."""
    return jax.tree.map(
        lambda d: NOFSDP if d in (None, NOFSDP, 0) else d - 1,
        dims_tree, is_leaf=lambda x: x is None)


@dataclass
class StepBundle:
    fn: Callable                       # jitted step
    mesh: Any
    model: Any
    param_defs: Any
    state_specs: Any                   # full PartitionSpec tree (for jit io)
    batch_specs: Any
    dims: Any                          # per-leaf scatter dims (None if repl.)
    zero: bool
    path: WidePath
    cache_defs: Any = None             # decode bundles only
    replan: Any = None                 # re-notes this bundle's traffic plan
    bucket_plan: Any = None            # BucketPlan when bucketed overlap is on
    compute_window: float = 0.0        # modeled overlappable seconds / microbatch

    def abstract_state(self):
        defs = self.param_defs
        params = tree_abstract(defs)
        opt = {
            "m": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params),
            "v": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        return {"params": params, "opt": opt}

    def init_state(self, seed: int = 0):
        params = tree_init(self.param_defs, seed)
        return {"params": params, "opt": init_opt_state(params)}


# ---------------------------------------------------------------------------
# gather hook construction (ZeRO-3 all-gather-at-use)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _ag_use(x, dim):
    """ZeRO-3 all-gather-at-use whose transpose reduce-scatters in f32.

    The f32 backward is (a) better numerics for the gradient reduction and
    (b) a workaround for an XLA-CPU CHECK-failure on sub-f32 reduce-scatter
    inside partial-manual shard_map (AllReducePromotion bug).
    """
    return jax.lax.all_gather(x, "data", axis=dim, tiled=True)


def _ag_fwd(x, dim):
    return _ag_use(x, dim), jnp.zeros((0,), x.dtype)


def _ag_bwd(dim, res, g):
    rs = jax.lax.psum_scatter(g.astype(jnp.float32), "data",
                              scatter_dimension=dim, tiled=True)
    return (rs.astype(res.dtype),)


_ag_use.defvjp(_ag_fwd, _ag_bwd)


def _make_gather(defs, dims_tree, zero: bool, has_data_axis: bool):
    """Returns (gather_layer, gather_top).

    gather_layer(lp): applied by models inside the layer scan; matched to the
    right dims subtree by pytree structure.
    gather_top(params): gathers non-scanned leaves (embed/head/norms/shared).
    """
    if not zero or not has_data_axis:
        return None, lambda p: p

    tables = []
    for key in ("blocks", "encoder"):
        if isinstance(defs, dict) and key in defs:
            src = dims_tree[key]
            if key == "encoder":  # ln_f is applied outside the layer scan
                src = {k: v for k, v in src.items() if k != "ln_f"}
            sub = _strip_layer_dim(src)
            leaves, td = jax.tree.flatten(sub)
            tables.append((td, leaves))

    def gather_leaf(x, d):
        if d is None or d == NOFSDP:
            return x
        return _ag_use(x, d)

    def gather_layer(lp):
        leaves, td = jax.tree.flatten(lp)
        for td_ref, dsub in tables:
            if td == td_ref:
                return jax.tree.unflatten(
                    td, [gather_leaf(x, d) for x, d in zip(leaves, dsub)])
        raise ValueError(f"gather: unknown layer structure {td}")

    def gather_top(params):
        out = {}
        for k, v in params.items():
            if k == "blocks":
                out[k] = v
            elif k == "encoder":
                enc = dict(v)
                dl = jax.tree.leaves({"ln_f": dims_tree[k]["ln_f"]},
                                     is_leaf=lambda x: x is None)
                enc["ln_f"] = gather_leaf(v["ln_f"], dl[0])
                out[k] = enc
            else:
                out[k] = _map_with_dims(gather_leaf, v, dims_tree[k])
        return out

    return gather_layer, gather_top


def _map_with_dims(fn, tree, dims):
    dim_leaves = jax.tree.leaves(dims, is_leaf=lambda x: x is None)
    leaves, td = jax.tree.flatten(tree)
    return jax.tree.unflatten(td, [fn(x, d) for x, d in zip(leaves, dim_leaves)])


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def build_train_step(rc: RunConfig, mesh, *, route=None,
                     site_groups=None, local_only=False) -> StepBundle:
    """`route` (a :class:`repro.core.topology.Route`) makes the cross-pod
    path multi-hop: per-hop links/knobs from the route's LinkProfiles, with
    the bottleneck leg driven by ``rc.comm`` (the autotuner's slot), and
    per-hop plans in telemetry.  `site_groups` (Topology.pod_groups) makes
    the cross-pod psum site-hierarchical: intra-site reduction first, only
    gateway pods cross the slow hop.  `local_only=True` builds the
    local-SGD step (``CommConfig.local_steps > 1``): the gradient sync
    stays inside each site (grouped pod psum over the LAN, no WAN stage,
    no bucketed overlap — there is nothing to hide) and the cross-site
    reconciliation is a separate K-step delta sync, see
    ``repro/core/localsgd.py``."""
    model = build_model(rc.model)
    defs = model.param_defs()
    manual = set(dp_axes_of(mesh))
    if local_only and rc.comm.mode != "hierarchical":
        raise ValueError(f"local-SGD local steps need comm mode "
                         f"'hierarchical', got {rc.comm.mode!r}")
    if site_groups is not None:
        npods = int(mesh.shape.get("pod", 1))
        total = sorted(p for g in site_groups for p in g)
        if "pod" not in mesh.axis_names:
            site_groups = None          # single-pod smoke: nothing to group
        elif total != list(range(npods)):
            raise ValueError(f"site_groups {site_groups} must tile the pod "
                             f"axis of size {npods}")
    tp = int(mesh.shape.get("model", 1))
    data_size = int(mesh.shape.get("data", 1))
    zero = bool(rc.train.zero1 and rc.comm.mode == "hierarchical"
                and "data" in manual and data_size > 1)
    fsdp_axes = ("data",) if zero else ()
    dims = tree_fsdp_dims(defs, data_size, tp)
    nones = jax.tree.map(lambda d: None, dims, is_leaf=lambda x: x is None)

    param_specs = tree_specs(defs, fsdp_axes=fsdp_axes,
                             fsdp_size=data_size if zero else 1, tp_size=tp)
    opt_specs = {"m": param_specs, "v": param_specs, "step": P()}
    state_specs = {"params": param_specs, "opt": opt_specs}

    dp = tuple(a for a in ("pod", "data") if a in manual)
    batch_specs = jax.tree.map(lambda _: P(dp), _batch_template(rc))

    # MPWide path over the pod axis (autotuned to the cross-pod payload);
    # a route turns it into the Forwarder chain, slow leg driven by rc.comm
    path = WidePath(axis="pod", comm=rc.comm, link=INTERPOD, name="train")
    if route is not None:
        path = path.with_hops(route.as_hops(bottleneck_comm=rc.comm))
    tc = rc.train
    m_micro = max(1, tc.microbatches)
    payload = _param_bytes(defs) // (data_size if zero else 1)
    pod_world = int(mesh.shape.get("pod", 1))
    n_chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    # exposure-aware build-time tuning: the sync can hide under one
    # microbatch of modeled compute, so the alpha-beta warm start minimizes
    # *exposed* seconds against that window, not total link seconds
    window = modeled_compute_window(rc.model, rc.shape, n_chips=n_chips,
                                    microbatches=m_micro)
    path = autotune_path(path, payload, world=pod_world,
                         compute_window=window)

    # ---- bucketed overlap setup (see repro/core/buckets.py) ---------------
    # * flush mode: the layer scan is split at bucket boundaries; a
    #   custom_vjp hook syncs each bucket during backprop (overlap even at
    #   microbatches=1).  Needs model support and an uncompressed wire —
    #   compressed wires keep the tail mode's bit-identical guarantee (and
    #   at TP>1 their nested shard_map cannot wrap per-segment hooks).
    # * tail mode (fallback): the post-backward sync goes bucket-by-bucket
    #   so the optimizer can consume bucket k while k+1 is in flight.
    bucket_bytes = path.bucket_bytes
    bucketed = bool(bucket_bytes > 0 and rc.comm.mode == "hierarchical"
                    and zero and not local_only)
    supports_flush = "flush_segments" in inspect.signature(
        model.loss).parameters
    use_flush = bool(bucketed and supports_flush
                     and rc.comm.compress == "none")
    stacked_tree = {k: jax.tree.map(lambda pd: k == "blocks", v,
                                    is_leaf=is_pd_leaf)
                    for k, v in defs.items()}
    plan = None
    stacked_flags = None
    if bucketed:
        eff_leaves, eff_dims = _eff_grad_leaves(defs, dims,
                                                data_size if zero else 1)
        raw_flags = [bool(f) for f in jax.tree.leaves(stacked_tree)]
        stacked_flags = (raw_flags if use_flush
                         else bk.bucketable_flags(eff_leaves, raw_flags,
                                                  eff_dims))
        plan = bk.plan_buckets(eff_leaves, stacked_flags, bucket_bytes)
        if not plan.layer_buckets:
            bucketed = use_flush = False
            plan = stacked_flags = None

    replan = None
    if rc.comm.mode != "flat" and not local_only:
        # telemetry: the per-step traffic plan is known at build time (f32
        # grads, ZeRO leaves scattered over "data"); recording it here keeps
        # MPW.Report populated even on single-pod runs that never trace the
        # cross-pod stage.  The bundle keeps the note as `replan` so a
        # trainer swapping back to a cached bundle can refresh the registry.
        replan = functools.partial(_note_path_plan, defs, dims, path,
                                   data_size if zero else 1, pod_world,
                                   stacked_flags=stacked_flags,
                                   window=window, m_micro=m_micro)
        replan()

    gather_layer, gather_top = _make_gather(defs, dims, zero, "data" in manual)
    dp_world = int(np.prod([mesh.shape[a] for a in manual])) if manual else 1
    # local-SGD: the per-step gradient mean is over the *site's* replicas
    # only (the sites' models diverge between delta syncs by design)
    sync_world = dp_world
    if local_only and site_groups is not None and "pod" in manual:
        sync_world = data_size * len(site_groups[0])
    dims_or_none = dims if zero else nones

    def _tp_wrapped(fn, specs):
        """Run a cross-pod sync under a fully-manual {"model"} shard_map
        when the wire is compressed and TP is real: quantize/pad/gather ops
        would otherwise let GSPMD replicate the "model"-sharded dims (§Perf
        P8: 16x inflation)."""
        if rc.comm.compress == "none" or tp <= 1:
            return fn
        tp_specs = jax.tree.map(lambda s: _manual_part(s, {"model"}), specs,
                                is_leaf=lambda x: isinstance(x, P))

        def wrapped(g):
            inner = jax.shard_map(fn, in_specs=(tp_specs,),
                                  out_specs=tp_specs,
                                  axis_names={"model"}, check_vma=False)
            return inner(g)
        return wrapped

    def _cross_pod(grads):
        if bucketed and not use_flush:
            # tail-mode buckets: one streamed psum per layer bucket, so the
            # bucketed optimizer below can start on bucket k while bucket
            # k+1's transfer is still in flight
            fn = lambda g: bk.bucketed_sync(g, path, stacked=stacked_tree,
                                            dims=dims,
                                            site_groups=site_groups)
        else:
            fn = lambda g: streamed_psum(g, path, dims=dims,
                                         site_groups=site_groups)
        return _tp_wrapped(fn, param_specs)(grads)

    rest_keys = tuple(k for k in defs if k != "blocks")

    def _sync_rest(grads):
        """Flush mode: blocks grads were synced during backprop by the
        segment hooks — only the top-level leaves (embed/head/norms/encoder,
        the rest bucket) still need the in-pod reduction + cross-pod psum."""
        rest = {k: grads[k] for k in rest_keys}
        rest_dims = {k: dims[k] for k in rest_keys}
        if "data" in manual:
            rest = _map_with_dims(
                lambda g, d: jax.lax.psum(g, "data") if d in (None, NOFSDP) else g,
                rest, rest_dims)
        rest_specs = {k: param_specs[k] for k in rest_keys}
        rest_bkt = len(plan.layer_buckets)
        fn = lambda g: streamed_psum(g, path, dims=rest_dims,
                                     site_groups=site_groups,
                                     tel_key=f"{path.key}/bkt{rest_bkt}")
        synced = _tp_wrapped(fn, rest_specs)(rest)
        return {**synced, "blocks": grads["blocks"]}

    def _intra_pod(grads):
        # local-SGD cross-pod stage: grouped psum inside each site (LAN
        # only); the WAN exchange is the K-step delta sync
        if "pod" not in manual:
            return grads
        groups = ([list(g) for g in site_groups]
                  if site_groups is not None else None)
        return jax.tree.map(
            lambda g: jax.lax.psum(g, "pod", axis_index_groups=groups),
            grads)

    def sync(grads):
        if rc.comm.mode == "flat":
            return flat_allreduce(grads, dp)
        if rc.comm.mode == "gateway":
            return gateway_allreduce(grads, path, ("data",))
        # hierarchical: replicated leaves still need the in-pod reduction
        if zero:
            if use_flush:
                return _sync_rest(grads)
            if "data" in manual:
                grads = _map_with_dims(
                    lambda g, d: jax.lax.psum(g, "data") if d in (None, NOFSDP) else g,
                    grads, dims)
            if local_only:
                return _intra_pod(grads)
            return _cross_pod(grads)
        if local_only:
            from repro.core.collectives import local_site_allreduce
            return local_site_allreduce(grads, path, ("data",), dims,
                                        site_groups=site_groups)
        from repro.core.collectives import hierarchical_allreduce
        return hierarchical_allreduce(grads, path, ("data",), dims,
                                      site_groups=site_groups)

    flush_segments = _make_flush_segments(
        defs, dims, path, plan, site_groups, manual,
        data_size if zero else 1) if use_flush else None

    def loss_fn(params, mb):
        p = gather_top(params)
        if flush_segments is not None:
            return model.loss(p, mb, gather=gather_layer,
                              flush_segments=flush_segments)
        return model.loss(p, mb, gather=gather_layer)

    _vg = jax.value_and_grad(loss_fn, has_aux=True)

    def grad_fn(p, mb):
        # f32 gradients from here on: f32 accumulation numerics, and all
        # syncs ship f32 (uniform wire dtype across comm modes; also avoids
        # the XLA-CPU bf16-collective bug in partial-manual shard_map).
        out, g = _vg(p, mb)
        return out, jax.tree.map(lambda x: x.astype(jnp.float32), g)

    def body(state, batch):
        params = state["params"]
        mbs = jax.tree.map(
            lambda x: x.reshape((m_micro, x.shape[0] // m_micro) + x.shape[1:]),
            batch)
        loss, metrics, grads = accum_grads(
            grad_fn, params, mbs,
            sync=sync, dims=dims_or_none, overlap=m_micro > 1)
        grads = jax.tree.map(lambda g: g / sync_world, grads)
        lr = lr_at(state["opt"]["step"], tc)
        # bucketed: update(bucket k) depends only on sync(bucket k) + the
        # clip-norm scalar, so the optimizer interleaves with in-flight
        # sync buckets instead of waiting for the whole tree
        new_params, new_opt, stats = adamw_update(
            grads, state["opt"], params, tc, lr,
            dims=dims_or_none, data_axes=dp,
            buckets=plan, stacked=stacked_flags)
        if manual:
            loss = jax.lax.psum(loss, tuple(manual)) / dp_world
        out_metrics = {"loss": loss, "lr": lr, **stats,
                       "aux_loss": metrics.get("aux_loss", jnp.float32(0.0))}
        return {"params": new_params, "opt": new_opt}, out_metrics

    if manual:
        manual_state_specs = jax.tree.map(
            lambda s: _manual_part(s, manual), state_specs,
            is_leaf=lambda x: isinstance(x, P))
        manual_batch_specs = jax.tree.map(lambda s: _manual_part(s, manual),
                                          batch_specs,
                                          is_leaf=lambda x: isinstance(x, P))
        stepped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(manual_state_specs, manual_batch_specs),
            out_specs=(manual_state_specs, P()),
            axis_names=manual, check_vma=False)
    else:
        stepped = body

    fn = jax.jit(
        stepped,
        in_shardings=(jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                                   is_leaf=lambda x: isinstance(x, P)),
                      jax.tree.map(lambda s: NamedSharding(mesh, s), batch_specs,
                                   is_leaf=lambda x: isinstance(x, P))),
        out_shardings=(jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                                    is_leaf=lambda x: isinstance(x, P)),
                       NamedSharding(mesh, P())),
        donate_argnums=(0,))
    return StepBundle(fn=fn, mesh=mesh, model=model, param_defs=defs,
                      state_specs=state_specs, batch_specs=batch_specs,
                      dims=dims_or_none, zero=zero, path=path, replan=replan,
                      bucket_plan=plan, compute_window=window)


def build_delta_sync(rc: RunConfig, mesh, bundle: StepBundle, *,
                     site_groups, member_pods, member_gateways):
    """Jitted cross-site local-SGD reconciliation for one membership epoch.

    Wraps :func:`repro.core.localsgd.delta_sync` in the same partial-manual
    shard_map as the train step (manual DP axes, compressed wires get the
    full-manual {"model"} inner wrap — §Perf P8).  Returns None when there
    is nothing to reconcile (no pod axis, or fewer than two member sites);
    the Trainer re-builds on every epoch change — membership is a
    trace-time constant of the executable.
    """
    from repro.core.localsgd import delta_sync
    manual = set(dp_axes_of(mesh))
    if ("pod" not in manual or site_groups is None
            or len(member_gateways) < 2):
        return None
    tp = int(mesh.shape.get("model", 1))
    pspecs = bundle.state_specs["params"]
    mspecs = jax.tree.map(lambda s: _manual_part(s, manual), pspecs,
                          is_leaf=lambda x: isinstance(x, P))

    def run(p, a):
        return delta_sync(p, a, bundle.path, dims=bundle.dims,
                          site_groups=site_groups, member_pods=member_pods,
                          member_gateways=member_gateways)

    def body(params, anchor):
        if rc.comm.compress == "none" or tp <= 1:
            return run(params, anchor)
        tp_specs = jax.tree.map(lambda s: _manual_part(s, {"model"}), pspecs,
                                is_leaf=lambda x: isinstance(x, P))
        inner = jax.shard_map(run, in_specs=(tp_specs, tp_specs),
                              out_specs=tp_specs, axis_names={"model"},
                              check_vma=False)
        return inner(params, anchor)

    stepped = jax.shard_map(body, mesh=mesh, in_specs=(mspecs, mspecs),
                            out_specs=mspecs, axis_names=manual,
                            check_vma=False)
    shard = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    return jax.jit(stepped, in_shardings=(shard(pspecs), shard(pspecs)),
                   out_shardings=shard(pspecs), donate_argnums=(0,))


def build_catchup(mesh, bundle: StepBundle, *, source_pod: int, target_pods):
    """Jitted rejoin catch-up: broadcast a surviving gateway's params onto
    the rejoined site's pods (see :func:`repro.core.localsgd.catchup`).
    Survivor pods pass through bit-untouched."""
    from repro.core.localsgd import catchup
    manual = set(dp_axes_of(mesh))
    if "pod" not in manual or not target_pods:
        return None
    pspecs = bundle.state_specs["params"]
    mspecs = jax.tree.map(lambda s: _manual_part(s, manual), pspecs,
                          is_leaf=lambda x: isinstance(x, P))

    def body(params):
        return catchup(params, bundle.path, source_pod=source_pod,
                       target_pods=target_pods)

    stepped = jax.shard_map(body, mesh=mesh, in_specs=(mspecs,),
                            out_specs=mspecs, axis_names=manual,
                            check_vma=False)
    shard = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    return jax.jit(stepped, in_shardings=(shard(pspecs),),
                   out_shardings=shard(pspecs), donate_argnums=(0,))


def _batch_template(rc: RunConfig) -> dict:
    tmpl = {"tokens": 0}
    if rc.model.vision_tokens and rc.shape.kind != "decode":
        tmpl["patch_embeds"] = 0
    if rc.model.encoder_layers and rc.shape.kind != "decode":
        tmpl["source_frames"] = 0
    return tmpl


def _param_bytes(defs) -> int:
    total = 0
    for pd in jax.tree.leaves(defs, is_leaf=is_pd_leaf):
        total += leaf_bytes_pd(pd)
    return total


def _eff_grad_leaves(defs, dims, shard: int):
    """(abstract leaves, effective scatter dims) of the cross-pod gradient
    payload: f32 on the wire, ZeRO leaves scattered over "data" as 1/shard
    slices — exactly what streamed_psum sees."""
    leaves = jax.tree.leaves(tree_abstract(defs))
    dim_leaves = jax.tree.leaves(dims, is_leaf=lambda x: x is None)
    eff_leaves, eff_dims = [], []
    for x, d in zip(leaves, dim_leaves):
        d = None if d in (None, NOFSDP) else d
        shape = list(x.shape)
        if d is not None and shard > 1 and shape[d] % shard == 0:
            shape[d] //= shard
        eff_leaves.append(jax.ShapeDtypeStruct(tuple(shape), jnp.float32))
        eff_dims.append(d if (d is not None and len(shape)) else None)
    return eff_leaves, eff_dims


def _make_flush_segments(defs, dims, path: WidePath, plan, site_groups,
                         manual, shard: int):
    """(layer bounds, per-bucket flush hooks) for the segmented layer scan.

    Each hook is a custom_vjp identity around one bucket's stacked-param
    slice; its backward casts the bucket's gradients to the f32 wire dtype,
    does the in-pod reduction for replicated leaves, and issues the bucket's
    cross-pod streamed psum under ``{key}/bkt{i}`` — at that point the
    backward of earlier layers has not run yet, so the transfer overlaps it.
    Chunk geometry is pinned to the *full* leaf's rows so bucketing leaves
    quantization blocks (int8 wire) unchanged.
    """
    from repro.core import streams as st
    blocks_eff, blocks_dims = _eff_grad_leaves(defs["blocks"], dims["blocks"],
                                               shard)
    blocks_ndims = st.normalize_dims(blocks_eff, blocks_dims)
    rows_full = [st.chunk_rows(x, d, path.chunk_bytes)
                 for x, d in zip(blocks_eff, blocks_ndims)]
    index_of = {(b.lo, b.hi): b.index for b in plan.layer_buckets}

    def make_sync(bi: int):
        def sync_seg(g):
            leaves, td = jax.tree.flatten(g)
            gf = [l.astype(jnp.float32) for l in leaves]
            if "data" in manual:
                gf = [jax.lax.psum(l, "data") if d is None else l
                      for l, d in zip(gf, blocks_dims)]
            chunks = st.plan_chunks(gf, blocks_ndims, path.chunk_bytes,
                                    rows=rows_full)
            synced = streamed_psum(gf, path, dims=blocks_dims,
                                   site_groups=site_groups,
                                   tel_key=f"{path.key}/bkt{bi}",
                                   chunks=chunks)
            return jax.tree.unflatten(
                td, [s.astype(l.dtype) for s, l in zip(synced, leaves)])
        return sync_seg

    bounds = plan.layer_bounds
    hooks = [flush_hook(make_sync(index_of[b])) for b in bounds]
    return bounds, hooks


def _note_path_plan(defs, dims, path: WidePath, shard: int,
                    world: int = 1, *, stacked_flags=None,
                    window: float = 0.0, m_micro: int = 1) -> None:
    """Record the path's static gradient-sync plan into telemetry.

    Mirrors what streamed_psum will see: gradients are f32 on the wire, and
    under ZeRO each scatterable leaf crosses pods as a 1/shard slice;
    `world` (the pod-axis size) feeds the modeled per-pod wire bytes of the
    configured (algo, compress).  With `stacked_flags` (bucketed overlap on)
    per-bucket plans land under ``{key}/bkt{i}``; `window` (modeled
    overlappable compute seconds per microbatch) feeds the ``exposed_s`` /
    ``overlapped_s`` overlap note — single-pod builds model the configured
    inter-pod link at the minimal 2-pod deployment.
    """
    from repro.core import streams as st
    from repro.core import telemetry as tel
    from repro.core.overlap import modeled_exposure
    eff_leaves, eff_dims = _eff_grad_leaves(defs, dims, shard)
    chunks = st.plan_chunks(eff_leaves, eff_dims, path.chunk_bytes)
    buckets = st.assign_streams(chunks, path.streams)
    tel.note_plan(path.key, **st.plan_summary(
        chunks, buckets, path.streams, path.chunk_bytes, path.comm.pacing,
        algo=path.comm.algo, world=world, compress=path.comm.compress))
    if path.hops:
        from repro.core.collectives import _note_hop_plans
        _note_hop_plans(path, eff_leaves, eff_dims)
    if stacked_flags is not None and path.bucket_bytes > 0:
        bk.note_bucket_plans(path, eff_leaves, eff_dims, None,
                             world=world, flags=stacked_flags)
    res = modeled_exposure(
        sum(st.leaf_bytes(x) for x in eff_leaves), path.link,
        streams=path.streams, chunk_bytes=path.chunk_bytes,
        pacing=path.comm.pacing, compute_window=window,
        bucket_bytes=path.bucket_bytes if stacked_flags is not None else 0,
        microbatches=m_micro, world=max(2, world),
        algo=path.comm.algo, compress=path.comm.compress)
    tel.note_overlap(path.key, res["exposed_s"], res["overlapped_s"])


# ---------------------------------------------------------------------------
# serve step (prefill / decode)
# ---------------------------------------------------------------------------

def cache_spec(pd: PD, *, batch_shardable: bool, tp: int, kv_heads: int,
               dp: tuple = ("pod", "data")) -> P:
    """Sharding for a cache leaf: batch over DP when divisible; the largest
    TP-compatible dim over "model" (kv_heads when divisible, else seq)."""
    entries: list = []
    kv_ok = kv_heads % tp == 0 if tp > 1 else False
    for a, s in zip(pd.axes, pd.shape):
        if a == "batch":
            entries.append(dp if (batch_shardable and dp) else None)
        elif a == "kv_heads" and kv_ok:
            entries.append("model")
        elif a == "seq" and not kv_ok and s % max(tp, 1) == 0:
            entries.append("model")
        elif a in ("ssm_heads", "conv_ch") and s % max(tp, 1) == 0:
            entries.append("model")
        else:
            entries.append(None)
    return P(*entries)


def build_serve_step(rc: RunConfig, mesh, kind: Optional[str] = None) -> StepBundle:
    """kind: "decode" (one token against a seq_len cache) or "prefill".

    Serving keeps params replicated over "data" whenever the TP-sharded
    copy fits HBM — the ZeRO layout would re-gather every layer's weights
    each decoded token (§Perf P4: decode was collective-bound purely on
    those gathers).  Only models whose TP shard exceeds the budget (dbrx)
    stay scattered.
    """
    kind = kind or rc.shape.kind
    model = build_model(rc.model)
    defs = model.param_defs()
    manual = set(dp_axes_of(mesh))
    tp = int(mesh.shape.get("model", 1))
    data_size = int(mesh.shape.get("data", 1))
    tp_shard_bytes = 2 * rc.model.param_count() // max(tp, 1)
    needs_zero = tp_shard_bytes > 8 * 2**30
    zero = bool(needs_zero and rc.train.zero1 and "data" in manual
                and data_size > 1)
    dims = tree_fsdp_dims(defs, data_size, tp)
    param_specs = tree_specs(defs, fsdp_axes=("data",) if zero else (),
                             fsdp_size=data_size if zero else 1, tp_size=tp)
    gather_layer, gather_top = _make_gather(defs, dims, zero, "data" in manual)

    B, S = rc.shape.global_batch, rc.shape.seq_len
    dp_world = int(np.prod([mesh.shape[a] for a in manual])) if manual else 1
    batch_shardable = B % max(dp_world, 1) == 0 and B >= dp_world and dp_world > 1
    dp = tuple(a for a in ("pod", "data") if a in manual)
    bspec = P(dp) if batch_shardable else P()

    if kind == "decode":
        cache_defs = model.cache_defs(B, S)
        cache_specs = jax.tree.map(
            lambda pd: cache_spec(pd, batch_shardable=batch_shardable, tp=tp,
                                  kv_heads=max(rc.model.num_kv_heads, 1),
                                  dp=dp),
            cache_defs, is_leaf=is_pd_leaf)

        # named, so its program runs as `jit_serve_decode`
        def serve_decode(params, cache, pos, tokens):
            p = gather_top(params)
            if getattr(pos, "ndim", 0) >= 1 and batch_shardable and manual:
                # per-sequence positions arrive replicated (full (B,));
                # slice this shard's rows to line up with its cache rows
                idx = jnp.int32(0)
                for a in dp:
                    idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
                b_local = tokens.shape[0]
                pos = jax.lax.dynamic_slice_in_dim(pos, idx * b_local, b_local)
            logits, new_cache = model.decode_step(p, cache, pos, tokens,
                                                  gather=gather_layer)
            return logits, new_cache

        in_specs_manual = (
            jax.tree.map(lambda s: _manual_part(s, manual), param_specs,
                         is_leaf=lambda x: isinstance(x, P)),
            jax.tree.map(lambda s: _manual_part(s, manual), cache_specs,
                         is_leaf=lambda x: isinstance(x, P)),
            P(), _manual_part(bspec, manual))
        out_specs_manual = (_manual_part(bspec, manual),
                            jax.tree.map(lambda s: _manual_part(s, manual),
                                         cache_specs,
                                         is_leaf=lambda x: isinstance(x, P)))
        stepped = jax.shard_map(serve_decode, mesh=mesh,
                                in_specs=in_specs_manual,
                                out_specs=out_specs_manual,
                                axis_names=manual,
                                check_vma=False) if manual else serve_decode
        shard = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                       is_leaf=lambda x: isinstance(x, P))
        fn = jax.jit(stepped,
                     in_shardings=(shard(param_specs), shard(cache_specs),
                                   NamedSharding(mesh, P()), shard(bspec)),
                     out_shardings=(shard(bspec), shard(cache_specs)),
                     donate_argnums=(1,))
        bundle = StepBundle(fn=fn, mesh=mesh, model=model, param_defs=defs,
                            state_specs={"params": param_specs, "cache": cache_specs},
                            batch_specs={"tokens": bspec}, dims=dims, zero=zero,
                            path=WidePath(axis="pod", comm=rc.comm, name="serve"))
        bundle.cache_defs = cache_defs
        return bundle

    # prefill
    def body(params, batch):
        p = gather_top(params)
        return model.prefill(p, batch, gather=gather_layer)

    batch_specs = jax.tree.map(lambda _: bspec, _batch_template(rc))
    # cache leaves all carry batch at dim 1: (layers/sites, B, ...)
    cspec = P(None, dp) if batch_shardable else P()
    from repro.models.registry import batch_abstract
    _, cache_shape = jax.eval_shape(
        lambda p, b: model.prefill(p, b, gather=None),
        tree_abstract(defs), batch_abstract(rc.model, rc.shape))
    cache_specs_out = jax.tree.map(lambda _: cspec, cache_shape)
    if manual:
        stepped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda s: _manual_part(s, manual), param_specs,
                                   is_leaf=lambda x: isinstance(x, P)),
                      jax.tree.map(lambda s: _manual_part(s, manual), batch_specs,
                                   is_leaf=lambda x: isinstance(x, P))),
            out_specs=(_manual_part(bspec, manual),
                       jax.tree.map(lambda s: _manual_part(s, manual),
                                    cache_specs_out,
                                    is_leaf=lambda x: isinstance(x, P))),
            axis_names=manual, check_vma=False)
    else:
        stepped = body
    shard = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    fn = jax.jit(stepped, in_shardings=(shard(param_specs), shard(batch_specs)),
                 out_shardings=(shard(bspec), shard(cache_specs_out)))
    return StepBundle(fn=fn, mesh=mesh, model=model, param_defs=defs,
                      state_specs={"params": param_specs},
                      batch_specs=batch_specs, dims=dims, zero=zero,
                      path=WidePath(axis="pod", comm=rc.comm, name="serve"))
