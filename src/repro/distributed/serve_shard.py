"""Mesh-native sharded serving (DESIGN.md §12).

Wraps the per-layer decode attention in an explicit ``shard_map`` over a
``("data", "model")`` mesh so one Server drives every device:

* **"data"** shards the continuous batch: decode slots, page-table rows,
  the raw append buffers' batch axis — and, in paged mode, the shared
  arena's *page axis*.  Data shard ``d`` of ``n_d`` owns global page ids
  ``[d * P_loc, (d+1) * P_loc)`` (``P_loc = pool_pages / n_d``), handed out
  by its own offset ``PagedBlockPool`` — page ids stay globally unique and
  a table entry identifies its owning shard by integer division alone.
  The scheduler allocates a row's pages from the row's own shard, so every
  page a live row references is device-local: no cross-shard softmax
  combine is ever needed, which is what keeps sharded greedy decoding
  **bit-identical** to the single-device run.
* **"model"** shards KV heads *inside attention only*.  Parameters stay
  replicated (a tensor-parallel matmul's ``psum`` would reorder float
  sums and break bit-identity); attention is embarrassingly parallel over
  ``Hkv``, and contiguous ``Hq`` chunks align with their KV groups because
  ``n_model`` must divide ``n_kv_heads``.  The per-head outputs are
  re-gathered (pure data movement) before the output projection.

The machinery registers as the ``"sharded"`` attention backend: the
scheduler pins the *live* decode state's spec to it, ``set_serve_mesh``
supplies the mesh + inner backend at trace time, and the backend dispatches
``shard_map(inner)`` — or falls straight through to the inner backend when
no mesh is set or a shape does not divide (e.g. the batch-1 gathered solo
states the prefix-cache path builds).

Chunked admission (DESIGN.md §13) under a mesh uses the *dense-state*
chunk path: each PREFILLING row chunks through a private replicated
batch-1 state and splices into the sharded arena at the finish, because
encode-to-page through a batch-1 view of the GSPMD-sharded arena would
re-partition page-axis reductions and risk bit drift.  Page reservations
still come from the row's own data shard up front, so chunk pages stay
shard-affine exactly like decode-flushed ones.

CPU testing recipe: export
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* python
starts, then build the mesh with ``repro.launch.mesh.make_serve_mesh``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.core import cache as kvcache
from repro.core.pool import STORE_FIELDS, PagedBlockPool
from repro.kernels import ops as kernel_ops

Array = jax.Array


# ---------------------------------------------------------------------------
# Mesh bookkeeping
# ---------------------------------------------------------------------------


def mesh_counts(mesh) -> tuple[int, int]:
    """(n_data, n_model) — missing axes count 1."""
    shape = dict(mesh.shape)
    return int(shape.get("data", 1)), int(shape.get("model", 1))


def validate_serve_mesh(mesh, cfg, max_slots: int) -> tuple[int, int]:
    """Check a serving mesh against the model + server shape, with
    actionable errors.  Returns (n_data, n_model)."""
    names = set(mesh.axis_names)
    if names != {"data", "model"}:
        raise ValueError(
            f"serving mesh wants axes ('data', 'model'), got {tuple(mesh.axis_names)}"
            " — build it with repro.launch.mesh.make_serve_mesh")
    n_d, n_m = mesh_counts(mesh)
    if cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"sharded serving supports dense/moe decode state, not "
            f"family={cfg.family!r}")
    n_kv = cfg.n_kv_heads or cfg.n_heads
    if n_kv % n_m:
        raise ValueError(
            f"mesh model axis ({n_m}) must divide n_kv_heads "
            f"({n_kv}); use a ({n_d * n_m},1) mesh for pure data "
            "parallelism")
    if cfg.n_heads % n_m:
        raise ValueError(
            f"mesh model axis ({n_m}) must divide n_heads ({cfg.n_heads})")
    if max_slots % n_d:
        raise ValueError(
            f"mesh data axis ({n_d}) must divide max_slots ({max_slots}): "
            "decode slots shard as contiguous per-shard chunks")
    return n_d, n_m


# ---------------------------------------------------------------------------
# Per-shard page accounting
# ---------------------------------------------------------------------------


class ShardedPagedPool:
    """``n_shards`` offset ``PagedBlockPool``\\ s fronting one global arena.

    Shard ``d`` hands out ids ``[d * per_shard, (d+1) * per_shard)`` —
    the slice of the arena's page axis that lives on data shard ``d`` once
    the arena is sharded ``P(..., "data", ...)``.  ``alloc`` must name its
    shard (the scheduler allocates from the row's shard); ``retain`` /
    ``release`` / ``refcount`` route by page id.  Aggregate accounting
    matches the flat pool's interface so scheduler admission logic and
    ``stats()`` consumers read it unchanged; the invariant
    ``sum(shard free) == free_pages`` is property-tested.
    """

    def __init__(self, n_pages: int, page_nbytes_per_layer, n_shards: int):
        if n_shards < 1:
            raise ValueError(f"need >= 1 shard, got {n_shards}")
        if n_pages % n_shards:
            raise ValueError(
                f"n_pages ({n_pages}) must divide over {n_shards} shards")
        self.n_pages = int(n_pages)
        self.per_shard = self.n_pages // n_shards
        self.page_nbytes_per_layer = tuple(int(b) for b in page_nbytes_per_layer)
        self.shards = [
            PagedBlockPool(self.per_shard, self.page_nbytes_per_layer,
                           offset=d * self.per_shard)
            for d in range(n_shards)
        ]

    def shard_of(self, page) -> int:
        return int(page) // self.per_shard

    # -- allocation (routed) -------------------------------------------------
    def alloc(self, n: int, shard: int = 0) -> list[int]:
        return self.shards[shard].alloc(n)

    def retain(self, pages) -> None:
        for p in pages:
            self.shards[self.shard_of(p)].retain([p])

    def release(self, pages) -> list[int]:
        freed: list[int] = []
        for p in pages:
            freed.extend(self.shards[self.shard_of(p)].release([p]))
        return freed

    def refcount(self, page) -> int:
        return self.shards[self.shard_of(page)].refcount(page)

    # -- aggregate accounting (flat-pool interface) --------------------------
    @property
    def free_pages(self) -> int:
        return sum(s.free_pages for s in self.shards)

    @property
    def live_pages(self) -> int:
        return sum(s.live_pages for s in self.shards)

    @property
    def high_water(self) -> int:
        return sum(s.high_water for s in self.shards)

    @property
    def bytes_per_page(self) -> int:
        return sum(self.page_nbytes_per_layer)

    @property
    def live_bytes(self) -> int:
        return self.live_pages * self.bytes_per_page

    @property
    def total_bytes(self) -> int:
        return self.n_pages * self.bytes_per_page

    def stats(self) -> dict:
        per = [s.stats() for s in self.shards]
        agg = {k: sum(p[k] for p in per) for k in per[0]
               if k not in ("bytes_per_page", "bytes_live_by_layer")}
        agg["bytes_per_page"] = self.bytes_per_page
        agg["bytes_live_by_layer"] = [
            sum(xs) for xs in zip(*(p["bytes_live_by_layer"] for p in per))]
        return agg

    def shard_stats(self) -> list[dict]:
        return [s.stats() for s in self.shards]


# ---------------------------------------------------------------------------
# Decode-state partition specs / shardings
# ---------------------------------------------------------------------------


def _is_cache(x) -> bool:
    return isinstance(x, kvcache.LayerKVCache)


def _cache_field_spec(name: str, arr, spec, lead: int,
                      n_d: int, n_m: int) -> P:
    """PartitionSpec for one LayerKVCache leaf under the serving mesh.

    Defensive by construction: an axis only shards when its extent matches
    the expected role AND the mesh axis divides it — anything else stays
    replicated, so odd shapes degrade instead of erroring inside pjit.
    """
    shp, nd = arr.shape, arr.ndim
    ent: list = [None] * nd

    def heads_ok(ax: int) -> bool:
        return ax < nd and shp[ax] > 0 and shp[ax] % n_m == 0

    if name in STORE_FIELDS:
        if nd - lead < 4:  # layout dummy scales (e.g. raw) stay replicated
            return P()
        if heads_ok(lead + 1):
            ent[lead + 1] = "model"
        if spec.paged:
            # shared arena: batch extent 1, pages shard over "data"
            if shp[lead + 2] == spec.pool_pages and spec.pool_pages % n_d == 0:
                ent[lead + 2] = "data"
        elif shp[lead] % n_d == 0:
            ent[lead] = "data"
        return P(*ent)
    if name in ("k_buf", "v_buf"):
        if shp[lead] % n_d == 0:
            ent[lead] = "data"
        if heads_ok(lead + 1):
            ent[lead + 1] = "model"
        return P(*ent)
    if name in ("n_flushed", "buf_len"):
        if shp[lead] % n_d == 0:
            ent[lead] = "data"
        return P(*ent)
    if name == "page_tab":
        if spec.paged and nd - lead == 2 and shp[lead] % n_d == 0:
            ent[lead] = "data"
            return P(*ent)
        return P()
    return P()


def cache_partition_specs(c: kvcache.LayerKVCache, mesh) -> kvcache.LayerKVCache:
    """LayerKVCache-shaped pytree of PartitionSpecs (stacked caches get a
    replicated leading layer axis automatically via ``lead``)."""
    n_d, n_m = mesh_counts(mesh)
    lead = c.n_flushed.ndim - 1
    specs = {f: _cache_field_spec(f, getattr(c, f), c.spec, lead, n_d, n_m)
             for f in c._FIELDS}
    return type(c)(**specs, spec=c.spec)


def decode_state_shardings(state, mesh):
    """Canonical ``NamedSharding`` tree for a Server's live decode state.

    The Server ``device_put``\\ s the freshly-initialized state against this
    tree and constrains every state-producing closure's output to it, so
    array placement is stable across steps (no resharding thrash, donation
    stays buffer-compatible).
    """

    def one(x):
        if _is_cache(x):
            specs = cache_partition_specs(x, mesh)
            return type(x)(
                **{f: NamedSharding(mesh, getattr(specs, f)) for f in x._FIELDS},
                spec=x.spec)
        return NamedSharding(mesh, P())

    return jax.tree.map(one, state, is_leaf=_is_cache)


def constrain_state(state, shardings):
    """``with_sharding_constraint`` a state tree leaf-by-leaf (inside jit)."""
    return jax.tree.map(jax.lax.with_sharding_constraint, state, shardings)


def override_backend(state, backend: str):
    """Rewrite every cache's ``attn_backend`` (specs are static aux data —
    e.g. ``pool.gather_pages`` keeps the live state's ``"sharded"`` pin on
    the batch-1 dense seed it builds, where the solo chunked-prefill
    closures need the inner backend)."""

    def one(c):
        if _is_cache(c):
            return c.with_spec(dataclasses.replace(c.spec, attn_backend=backend))
        return c

    return jax.tree.map(one, state, is_leaf=_is_cache)


# ---------------------------------------------------------------------------
# The "sharded" attention backend
# ---------------------------------------------------------------------------

# Trace-time context for the backend below.  The Server sets it in __init__
# (before tracing its closures) and re-asserts it at the top of step();
# per-server jit closures capture whatever was current when they traced.
_CTX: dict = {"mesh": None, "inner": "auto"}


def set_serve_mesh(mesh, inner: str = "auto") -> None:
    """Bind the serving mesh + inner backend the ``"sharded"`` backend
    wraps.  ``mesh=None`` makes it a pass-through to ``inner``."""
    _CTX["mesh"] = mesh
    _CTX["inner"] = inner or "auto"


def _resolve_inner(layout) -> str:
    inner = kernel_ops.resolve_backend(_CTX["inner"], layout)
    if inner == "sharded":  # self-nesting (e.g. REPRO_ATTN_BACKEND=sharded)
        inner = "xla"
    return inner


@kernel_ops.register_backend("sharded")
def _sharded_backend(cache, q: Array, scale: float | None = None) -> Array:
    """shard_map the inner decode-attention backend over (data, model).

    Falls through to the inner backend directly when no mesh is bound or a
    shape does not divide the mesh — notably the batch-1 gathered solo
    states of the prefix-cache admission path, which inherit the live
    spec's ``"sharded"`` pin but run on replicated arrays.
    """
    mesh = _CTX["mesh"]
    spec = cache.spec
    inner = _resolve_inner(spec.impl)
    if mesh is None:
        return kernel_ops._BACKENDS[inner](cache, q, scale)
    n_d, n_m = mesh_counts(mesh)
    B, Hq, _ = q.shape
    Hkv = cache.k_buf.shape[1]
    if (B % n_d or Hkv % n_m or Hq % n_m
            or (spec.paged and spec.pool_pages % n_d)):
        return kernel_ops._BACKENDS[inner](cache, q, scale)

    p_loc = spec.pool_pages // n_d if spec.paged else 0

    def body(c, ql):
        lspec = dataclasses.replace(spec, attn_backend=inner)
        if spec.paged:
            # Each shard holds pages [base, base + p_loc) of the arena;
            # translate the (global-id) table to local ids and mark blocks
            # hosted elsewhere unassigned — the attention paths' validity
            # guards make those contribute nothing.  Scheduler invariant:
            # a row's pages all come from the row's own data shard, so the
            # rows this shard computes never lose a live block.
            base = jax.lax.axis_index("data") * p_loc
            pt = c.page_tab
            ptl = jnp.where((pt >= base) & (pt < base + p_loc), pt - base, -1)
            lspec = dataclasses.replace(lspec, pool_pages=p_loc)
            c = dataclasses.replace(c, page_tab=ptl, spec=lspec)
        else:
            c = c.with_spec(lspec)
        return kernel_ops._BACKENDS[inner](c, ql, scale)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(cache_partition_specs(cache, mesh), P("data", "model", None)),
        out_specs=P("data", "model", None),
        check_vma=False)
    o = fn(cache, q)
    # Pure all-gather of the head axis before o_proj: replicated weights +
    # per-head-exact attention keep greedy outputs bit-identical.
    return jax.lax.with_sharding_constraint(
        o, NamedSharding(mesh, P("data", None, None)))
