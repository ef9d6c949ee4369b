"""Step builders: jitted train_step / prefill / decode with full shardings.

These are shared by the real launchers (train.py / serve.py) and the dry-run:
the dry-run lowers exactly what the launcher would execute.

Two gradient-collective paths exist for training:

  * the default jitted step, where GSPMD derives the gradient reductions from
    the in/out shardings (raw XLA collectives); and
  * :func:`build_dp_train_step` — the *offloaded* path: the step runs under
    ``shard_map`` over the data-parallel mesh axes and every collective the
    application issues (gradient allreduce, metric means, the scan-shaped
    per-rank example offset) is an explicit
    :class:`~repro.core.packet.CollectiveDescriptor` dispatched through
    :class:`~repro.offload.OffloadEngine` — the paper's contract, with the
    *training step's own collectives* as the offloaded schedule rather than a
    side benchmark. Built with ``engine=None`` the same step body runs its
    collectives as raw per-axis ``lax`` reductions in the identical logical
    order, giving a bitwise reference for the engine path.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.packet import CollType
from repro.models import ModelApi, input_specs
from repro.offload import planner
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.sharding.rules import batch_specs, cache_specs, param_specs, zero1_specs
from repro.sharding.specs import Topology, plan_spec, use_topology


def _sharding(topo: Topology, spec_tree):
    if topo.mesh is None:
        return None
    return jax.tree.map(
        lambda s: NamedSharding(topo.mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )


def build_train_step(
    api: ModelApi,
    topo: Topology,
    shape: ShapeConfig,
    opt_cfg: Optional[AdamWConfig] = None,
    *,
    use_offload_engine: bool = False,
    engine: Any = None,
):
    """Returns (step_fn, arg_shapes, shardings) for one optimizer step.

    With ``use_offload_engine=True`` (and a mesh), the step is built by
    :func:`build_dp_train_step`: gradient/metric collectives dispatch through
    the given :class:`~repro.offload.OffloadEngine` as planned descriptors
    instead of GSPMD-derived reductions. Without a mesh the flag is a no-op
    (there is nothing to reduce over).
    """
    opt_cfg = opt_cfg or AdamWConfig()
    if use_offload_engine and topo.mesh is not None:
        if engine is None:
            raise ValueError(
                "use_offload_engine=True requires an OffloadEngine "
                "(see repro.launch.offload_runtime.build_offload_engine)"
            )
        return build_dp_train_step(api, topo, shape, opt_cfg, engine=engine)
    cfg = api.cfg

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(api.loss, has_aux=True)(
            params, batch
        )
        new_params, new_opt, stats = adamw_update(
            grads, opt_state, params, opt_cfg
        )
        out = {"loss": loss, **metrics, **stats}
        return new_params, new_opt, out

    pshapes = api.param_shapes()
    oshapes = jax.eval_shape(init_opt_state, pshapes)
    bshapes = input_specs(cfg, shape)

    pspec = param_specs(pshapes, cfg, topo)
    ospec = {
        "m": zero1_specs(pspec, pshapes, topo),
        "v": zero1_specs(pspec, pshapes, topo),
        "master": zero1_specs(pspec, pshapes, topo),
        "count": jax.sharding.PartitionSpec(),
    }
    bspec = batch_specs(bshapes, topo)

    jitted = jax.jit(
        train_step,
        in_shardings=(
            _sharding(topo, pspec),
            _sharding(topo, ospec),
            _sharding(topo, bspec),
        ),
        out_shardings=(
            _sharding(topo, pspec),
            _sharding(topo, ospec),
            None,
        ),
        donate_argnums=(0, 1),
    )
    return jitted, (pshapes, oshapes, bshapes), (pspec, ospec, bspec)


def _null_topo() -> Topology:
    # model-internal shard() annotations are global-sharding constraints;
    # inside shard_map's manual context they must be no-ops
    return Topology(mesh=None, batch_axes=("data",), model_axis=None)


def build_dp_train_step(
    api: ModelApi,
    topo: Topology,
    shape: ShapeConfig,
    opt_cfg: Optional[AdamWConfig] = None,
    *,
    engine: Any = None,
):
    """Data-parallel train step with application-issued collectives.

    The step body runs under ``shard_map`` over the topology's DP axes with
    params/optimizer state replicated and the batch sharded in the *plan's
    logical rank order* (``plan_spec``), so a tuned non-identity axis split
    needs no hand layout. Per step it issues four collectives:

      1. ALLREDUCE(sum) of the gradient pytree over the DP axes,
      2. ALLREDUCE(sum) of the loss/metric stack,
      3. EXSCAN(sum) of the per-rank example count — each rank's global
         example offset, the paper's primitive on the training path,
      4. ALLREDUCE(max) of offset+count — total examples seen this step.

    The step is three programs, the paper's host/NIC split:

      * ``local``  — jitted shard_map: per-rank fwd/bwd, emits the stacked
        ``(p, ...)`` contribution pytree (leading axis in the collective
        plan's logical rank order, sharded by ``plan_spec``);
      * ``collectives`` — with ``engine`` set, each collective is an encoded
        CollectiveDescriptor dispatched *per step* through
        ``OffloadEngine.offload`` in driver mode (planned multi-axis
        descriptors when the DP span is 2-3 mesh axes): step 1 compiles and
        caches the schedule programs, every later step is a plan-cache hit,
        and a remesh-cleared cache repopulates from these same descriptors
        on the next step. With ``engine=None`` a single prebuilt shard_map
        program runs raw per-axis ``lax`` reductions chained
        innermost-logical-level first — exactly the planned ALLREDUCE phase
        order — making the two paths bitwise comparable;
      * ``update`` — jitted AdamW on the reduced gradients.

    Requires a pure-DP mesh (``model_size == 1``): with tensor parallelism
    the gradient reductions are interleaved with the model's own collectives
    and belong to the GSPMD path.
    """
    opt_cfg = opt_cfg or AdamWConfig()
    mesh = topo.mesh
    if mesh is None:
        raise ValueError("build_dp_train_step requires a mesh topology")
    if topo.model_size > 1:
        raise ValueError(
            "the offload-engine train step is data-parallel only; "
            f"model axis has size {topo.model_size} (use the GSPMD path)"
        )
    cfg = api.cfg
    # size-1 axes carry no collective traffic; drop them from the DP span
    dp_names = tuple(
        a for a in topo.batch_axes if int(mesh.shape[a]) > 1
    )
    dp_sizes = tuple(int(mesh.shape[a]) for a in dp_names)
    dp = int(np.prod(dp_sizes)) if dp_sizes else 1
    k = len(dp_names)

    pshapes = api.param_shapes()
    oshapes = jax.eval_shape(init_opt_state, pshapes)
    bshapes = input_specs(cfg, shape)
    grad_bytes = sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(pshapes)
    )
    loss_s, aux_s = jax.eval_shape(api.loss, pshapes, bshapes)
    metric_bytes = 4 * (1 + len(jax.tree.leaves(aux_s)))

    # the gradient allreduce dominates the payload, so its tuned split
    # decides the step's logical axis order — and thereby the data layout
    # every other collective (and the batch sharding) follows
    order = (
        planner.plan_axis_order(CollType.ALLREDUCE, dp_sizes, grad_bytes)
        if k > 1
        else tuple(range(k))
    )
    layout = planner.PlanLayout(sizes=dp_sizes, order=order) if k else None
    names_l = layout.spec_axes(dp_names) if k else ()
    sizes_l = layout.logical_sizes if k else ()

    rep = lambda tree: jax.tree.map(lambda _: P(), tree)  # noqa: E731
    stacked = P(names_l[0] if k == 1 else names_l) if k else P()

    def bspec_one(leaf):
        nd = len(leaf.shape)
        if k and nd >= 1 and leaf.shape[0] % dp == 0 and leaf.shape[0] > 1:
            return plan_spec(layout, dp_names, ndim=nd)
        return P(*([None] * nd))

    bspec = jax.tree.map(bspec_one, bshapes)
    pspec, ospec = rep(pshapes), rep(oshapes)

    # --- program 1: per-rank fwd/bwd, stacked contributions out ----------
    def local_body(params, batch):
        with use_topology(_null_topo()):
            (loss, metrics), grads = jax.value_and_grad(
                api.loss, has_aux=True
            )(params, batch)
        count = jnp.asarray(batch["tokens"].shape[0], jnp.float32)
        stack = {
            "grads": grads,
            "metrics": {"loss": loss, **metrics},
            "count": count,
        }
        return jax.tree.map(lambda a: jnp.asarray(a)[None], stack)

    local_fn = jax.jit(
        shard_map(
            local_body,
            mesh=mesh,
            in_specs=(pspec, bspec),
            out_specs=stacked,
            check_vma=False,
        )
    )

    # --- program 2: the collectives the application issues ---------------
    if engine is not None and k > 0:
        if k > 1:
            mk = partial(engine.make_descriptor, axes=dp_sizes, split=order)
        else:
            mk = partial(engine.make_descriptor, p=dp)
        grad_desc = mk("ALLREDUCE", payload_bytes=grad_bytes, op="sum")
        metric_desc = mk(
            "ALLREDUCE", payload_bytes=metric_bytes, op="sum", comm_id=1
        )
        offset_desc = mk("EXSCAN", payload_bytes=4, op="sum", comm_id=2)
        seen_desc = mk("ALLREDUCE", payload_bytes=4, op="max", comm_id=3)
        axis_arg = dp_names if k > 1 else dp_names[0]

        def collectives(stack):
            off = partial(engine.offload, axis_name=axis_arg, mesh=mesh)
            gsum = off(grad_desc, stack["grads"])
            msum = off(metric_desc, stack["metrics"])
            offset = off(offset_desc, stack["count"])
            seen = off(seen_desc, offset + stack["count"])
            return gsum, msum, seen

    elif k > 0:

        def _chain(tree, reduce_fn):
            # innermost logical level first — the planned ALLREDUCE phase
            # order, so raw and engine paths associate identically
            for name in reversed(names_l):
                tree = jax.tree.map(lambda g, n=name: reduce_fn(g, n), tree)
            return tree

        def raw_body(stack):
            stack = jax.tree.map(lambda a: a[0], stack)
            gsum = _chain(stack["grads"], lax.psum)
            msum = _chain(stack["metrics"], lax.psum)
            rank = jnp.int32(0)
            for name, size in zip(names_l, sizes_l):
                rank = rank * size + lax.axis_index(name)
            count = stack["count"]
            offset = count * rank.astype(count.dtype)  # equal per-rank counts
            seen = _chain(offset + count, lax.pmax)
            return jax.tree.map(
                lambda a: jnp.asarray(a)[None], (gsum, msum, seen)
            )

        raw_fn = jax.jit(
            shard_map(
                raw_body,
                mesh=mesh,
                in_specs=(stacked,),
                out_specs=stacked,
                check_vma=False,
            )
        )

        def collectives(stack):
            return raw_fn(stack)

    else:

        def collectives(stack):
            return stack["grads"], stack["metrics"], stack["count"]

    # --- program 3: optimizer update on the reduced gradients ------------
    def update_body(params, opt_state, gsum, msum, seen):
        grads = jax.tree.map(
            lambda a: (a[0] / dp).astype(a.dtype), gsum
        )
        mstack = jax.tree.map(lambda a: a[0] / dp, msum)
        new_params, new_opt, stats = adamw_update(
            grads, opt_state, params, opt_cfg
        )
        out = {**mstack, **stats, "examples_seen": seen[0]}
        return new_params, new_opt, out

    # donate params/opt like the GSPMD path does — the update consumes them
    update_fn = jax.jit(update_body, donate_argnums=(0, 1))

    def step_fn(params, opt_state, batch):
        stack = local_fn(params, batch)
        gsum, msum, seen = collectives(stack)
        return update_fn(params, opt_state, gsum, msum, seen)

    return step_fn, (pshapes, oshapes, bshapes), (pspec, ospec, bspec)


def build_prefill_step(api: ModelApi, topo: Topology, shape: ShapeConfig):
    cfg = api.cfg
    bshapes = input_specs(cfg, shape)
    pshapes = api.param_shapes()
    pspec = param_specs(pshapes, cfg, topo)
    bspec = batch_specs(bshapes, topo)

    def prefill(params, batch):
        return api.prefill(params, batch)

    # output cache sharding: same rules as decode caches
    cshapes = jax.eval_shape(
        lambda p, b: api.prefill(p, b)[1], pshapes, bshapes
    )
    cspec = cache_specs(cshapes, cfg, topo)
    lspec = batch_specs(
        jax.eval_shape(lambda p, b: api.prefill(p, b)[0], pshapes, bshapes),
        topo,
    )
    jitted = jax.jit(
        prefill,
        in_shardings=(_sharding(topo, pspec), _sharding(topo, bspec)),
        out_shardings=(_sharding(topo, lspec), _sharding(topo, cspec)),
    )
    return jitted, (pshapes, bshapes), (pspec, bspec)


def build_decode_step(api: ModelApi, topo: Topology, shape: ShapeConfig):
    cfg = api.cfg
    bshapes = input_specs(cfg, shape)  # {token, cache, cache_len}
    pshapes = api.param_shapes()
    pspec = param_specs(pshapes, cfg, topo)
    cspec = cache_specs(bshapes["cache"], cfg, topo)
    tspec = batch_specs(bshapes["token"], topo)

    def decode(params, token, cache, cache_len):
        return api.decode_step(params, token, cache, cache_len)

    jitted = jax.jit(
        decode,
        in_shardings=(
            _sharding(topo, pspec),
            _sharding(topo, tspec),
            _sharding(topo, cspec),
            None,
        ),
        out_shardings=(
            _sharding(topo, tspec),
            _sharding(topo, cspec),
        ),
        donate_argnums=(2,),
    )
    return jitted, (pshapes, bshapes), (pspec, cspec)
