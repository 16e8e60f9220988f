"""Distributed train/serve step builders.

``make_train_step`` returns a pjit-compiled step with:
  * FSDP×TP parameter shardings from the logical-axis rules,
  * optional gradient accumulation over microbatches (scan),
  * optional remat (activation checkpointing) of layer bodies,
  * optional cross-pod error-feedback gradient compression (shard_map over
    the "pod" axis with the in-pod axes left to the SPMD partitioner).

``make_serve_steps`` returns pjit'd (prefill, decode) closures over the
compressed-cache serving path.

Both builders can also return the *unjitted* step plus the sharding trees,
which is what launch/dryrun.py lowers against ShapeDtypeStructs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shd
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import adamw, grad_compress


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    remat: bool = True
    microbatches: int = 1
    q_chunk: int = 2048
    kv_chunk: int = 2048
    unroll: bool = False
    cross_pod_grad_compress: bool = False
    opt: adamw.AdamWConfig = dataclasses.field(default_factory=adamw.AdamWConfig)


def shape_of(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def build_train_artifacts(cfg: ModelConfig, mesh: Mesh, scfg: TrainStepConfig,
                          batch_shape: dict[str, jax.ShapeDtypeStruct]):
    """Returns (step_fn, state_shapes, in_shardings, out_shardings).

    state = (params, opt_state); step(state, batch) -> (state, metrics).
    Everything is shape-only: the caller decides whether to init for real
    (training) or lower against ShapeDtypeStructs (dry-run).
    """
    rules = shd.train_rules(cfg, mesh)
    shd.set_ambient_mesh(mesh)  # enables activation constraints at trace time
    pshapes, axes = shapes_and_axes(cfg)
    pshard = shd.make_param_shardings(axes, pshapes, rules, mesh)
    ostate_shapes = jax.eval_shape(adamw.init, pshapes)
    oshard = adamw.AdamWState(
        step=shd.replicated(mesh), mu=pshard, nu=pshard)

    bshard = {k: shd.batch_sharding(mesh, v) for k, v in batch_shape.items()}

    err_shapes = None
    eshard = None
    if scfg.cross_pod_grad_compress and "pod" in mesh.axis_names:
        err_shapes = jax.eval_shape(grad_compress.init_error_state, pshapes)
        eshard = jax.tree.map(lambda s: s, pshard)  # error buf mirrors params

    def loss_fn(params, batch):
        loss, parts = M.lm_loss(
            params, cfg, batch, remat=scfg.remat,
            q_chunk=scfg.q_chunk, kv_chunk=scfg.kv_chunk, unroll=scfg.unroll)
        return loss, parts

    def grads_of(params, batch):
        if scfg.microbatches <= 1:
            (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            return loss, parts, grads
        # gradient accumulation: split batch on the leading axis
        mb = scfg.microbatches
        da = shd.data_axes(mesh)

        def split(x):
            y = x.reshape(mb, x.shape[0] // mb, *x.shape[1:])
            # keep each microbatch slice sharded like the original batch —
            # otherwise SPMD falls back to full rematerialization
            return jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, P(None, da, *([None] * (y.ndim - 2)))))

        mbatch = jax.tree.map(split, batch)

        def acc(carry, bi):
            g_sum, l_sum = carry
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, bi)
            g_sum = jax.tree.map(jnp.add, g_sum, g)
            return (g_sum, l_sum + loss), None

        g0 = jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                jnp.zeros(x.shape, jnp.float32), s),
            params, pshard)
        (g_sum, l_sum), _ = jax.lax.scan(acc, (g0, jnp.zeros((), jnp.float32)), mbatch)
        grads = jax.tree.map(lambda g: (g / mb).astype(jnp.float32), g_sum)
        return l_sum / mb, {"aux_loss": jnp.zeros((), jnp.float32),
                            "ce": l_sum / mb}, grads

    def grads_pod_compressed(params, batch, err):  # pragma: no cover
        """Fully-manual pod-axis variant: computes grads with the pod axis
        MANUAL so the cross-pod all-reduce itself carries compressed data.
        BLOCKED upstream: XLA's SPMD partitioner CHECK-fails
        (spmd_partitioner_util.cc PartitionGather) when partitioning this
        model under a partial-auto shard_map on the host platform — the
        active path compresses after the in-pod reduction instead, which
        preserves the error-feedback numerics; the transport-level byte
        saving is accounted analytically in EXPERIMENTS.md §Perf."""
        from jax import shard_map

        def per_pod(params, batch, err):
            (loss, parts), g = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            gq, e_new = grad_compress.tree_compress_decompress(g, err)
            g_red = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"), gq)
            loss = jax.lax.pmean(loss, "pod")
            parts = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"), parts)
            return loss, parts, g_red, e_new

        pspec = jax.tree.map(lambda _: P(), params)
        bspec = jax.tree.map(lambda _: P("pod"), batch)
        espec = jax.tree.map(lambda _: P(), err)
        parts_spec = {"aux_loss": P(), "ce": P()}
        fn = shard_map(per_pod, mesh=mesh,
                       in_specs=(pspec, bspec, espec),
                       out_specs=(P(), parts_spec, pspec, espec),
                       axis_names={"pod"}, check_vma=False)
        return fn(params, batch, err)

    def step(state, batch):
        params, opt_state, err = state
        loss, parts, grads = grads_of(params, batch)
        if err is not None:
            grads, err = _cross_pod_compressed_allreduce(grads, err, mesh, pshard)
        new_params, new_opt, metrics = adamw.update(scfg.opt, grads, opt_state, params)
        metrics = {"loss": loss, **parts, **metrics}
        return (new_params, new_opt, err), metrics

    state_shapes = (pshapes, ostate_shapes, err_shapes)
    in_shardings = ((pshard, oshard, eshard), bshard)
    out_shardings = ((pshard, oshard, eshard), None)  # metrics: XLA's choice
    return step, state_shapes, in_shardings, out_shardings


def _cross_pod_compressed_allreduce(grads, err, mesh: Mesh, pshard):
    """Error-feedback int8 compression on the pod axis (shard_map, other axes
    auto).  Gradients arrive already reduced over in-pod data axes by the
    SPMD partitioner; only the pod-axis reduction is intercepted here."""
    from jax import shard_map

    def per_pod(g_tree, e_tree):
        gq, e_new = grad_compress.tree_compress_decompress(g_tree, e_tree)
        g_red = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"), gq)
        return g_red, e_new

    # Partial-manual shard_map: only "pod" is manual; in/out specs may refer
    # to manual axes only.  Gradients/error state are replicated across pods
    # (pure-DP pod axis), hence P() per leaf; in-pod (data/model) shardings
    # stay under the automatic partitioner.
    specs_g = jax.tree.map(lambda _: P(), pshard)
    fn = shard_map(
        per_pod, mesh=mesh,
        in_specs=(specs_g, specs_g), out_specs=(specs_g, specs_g),
        axis_names={"pod"}, check_vma=False)
    return fn(grads, err)


def shapes_and_axes(cfg: ModelConfig):
    """(param ShapeDtypeStructs, logical-axes tree) without any allocation.

    ``init_params`` runs abstractly under eval_shape; the axes tree is pure
    Python built during tracing, captured by side effect.
    """
    from repro.models import layers as L

    box = {}
    dtype = L.dtype_of(cfg.dtype)

    def f(k):
        p, a = M.init_params(cfg, k, dtype)
        box["axes"] = a
        return p

    pshapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return pshapes, box["axes"]


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def build_serve_artifacts(cfg: ModelConfig, mesh: Mesh, *, batch: int,
                          prompt_len: int, max_seq: int,
                          q_chunk: int = 2048, kv_chunk: int = 2048,
                          unroll: bool = False):
    """Returns dict with prefill/decode step fns + sharding trees."""
    rules = shd.serve_rules(cfg, mesh)
    shd.set_ambient_mesh(mesh)
    pshapes, axes = shapes_and_axes(cfg)
    pshard = shd.make_param_shardings(axes, pshapes, rules, mesh)

    state_shapes = jax.eval_shape(
        lambda: M.init_decode_state(cfg, batch, max_seq))
    sshard = shd.cache_shardings(state_shapes, mesh)

    def prefill_step(params, batch_in):
        logits, state = M.prefill(params, cfg, batch_in, max_seq,
                                  q_chunk=q_chunk, kv_chunk=kv_chunk)
        return logits[:, -1], state

    def decode_step(params, tokens, position, state):
        return M.decode_step(params, cfg, tokens, position, state)

    return dict(
        prefill=prefill_step, decode=decode_step,
        pshapes=pshapes, pshard=pshard,
        state_shapes=state_shapes, sshard=sshard, rules=rules)
