"""Flat gradient/parameter slabs — the wire and aggregation format.

A *slab* is one contiguous ``(P_pad,)`` array holding every leaf of a
pytree: leaves in ``jax.tree`` flatten order, raveled C-order,
concatenated, and zero-padded so ``P_pad`` is a multiple of the Pallas
flush tile (:data:`repro.kernels.hybrid_aggregate.TILE_P`).  Workers
flatten a gradient **once** and ship the slab; the server holds each
incoming slab in one of ``K_max`` staging slots (a reference, not a
copy, when the slab is already on the device in the staging dtype) and
applies every flush through **one** jitted, donated executable that
reads the ``K_max`` rows where they lie, regardless of how many
gradients K the flush aggregates.  The same layout is what a
multi-process transport puts on the wire (one buffer, no per-leaf
framing).

The codec is dtype-aware: it keeps a **per-leaf dtype map** (decode
restores every leaf's original dtype exactly) and carries a declared
**aggregation dtype** — ``slab_dtype`` ``"f32"`` (the default, and the
historical format: byte-identical slabs to the pre-mixed-precision
codec) or ``"bf16"`` (half the bytes on the wire and in staging rows).
Whatever the slab dtype, the aggregator's *master* params slab stays
float32 and the flush reduction runs in float32 — bf16 trades wire and
staging bandwidth, never accumulator precision.

Layout::

    offset 0         sizes[0]        sizes[0]+sizes[1]   ...        P  P_pad
    |  leaf 0 (ravel) | leaf 1 (ravel) |  ...  | leaf L-1 | 0-padding |

Multi-million-parameter slabs can additionally be **sharded along P**
into tile-aligned chunks (:class:`SlabAggregator` ``shards=``): each
chunk is staged on, and flushed by its own donated executable on (one
per distinct chunk shape), a local device in round-robin order, so a
big model's staging traffic spreads across the host topology instead of
funneling through one device.  ``shards=1`` (the default for small
slabs) is the historical single-device path, bit for bit.

Donation rules (enforced by :class:`SlabAggregator`, relied on by the
cluster server):

* the aggregator's private params slab (and optimizer moments) are
  donated into the flush — they are updated in place and must never
  escape the aggregator.  Staged rows are read, never donated: a held
  gradient slab is not written by the aggregator;
* everything handed to callers (the published params slab, decoded
  trees) is a *fresh* executable output, never an alias of a donated
  buffer, so it stays valid across later flushes;
* long-lived consumers (checkpoints, metric snapshots) must still copy
  to host (``jax.device_get``) before releasing the server lock — see
  ``ParameterServer.snapshot``.

Backend matrix for the flush's inner reduction:

============  =======================================================
TPU           :func:`repro.kernels.hybrid_aggregate.flush_pallas`
              over the K_max rows (masked: zero-weight slots beyond K
              contribute exactly 0)
CPU / other   jnp fallback — a statically unrolled masked fold, bitwise
              identical to the legacy per-leaf fold for uniform weights
tests         the Pallas kernel under ``interpret=True``
============  =======================================================
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.hybrid_aggregate import (TILE_P, flush_adamw_pallas,
                                            flush_momentum_pallas,
                                            flush_pallas)
from repro.optim.optimizers import bias_correction
from repro.optim.slab_form import SlabOptimizer

# declared aggregation dtypes: spec/CLI name -> jnp dtype.  "f32" is the
# historical pinned format (byte-identical slabs to the pre-dtype-aware
# codec); "bf16" halves wire + staging bytes at documented precision cost
SLAB_DTYPES: Dict[str, Any] = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def resolve_slab_dtype(name: str):
    """``"f32"``/``"bf16"`` (or any alias numpy/jnp resolves to the same
    dtype) -> the jnp slab dtype."""
    if name in SLAB_DTYPES:
        return SLAB_DTYPES[name]
    dt = jnp.dtype(name)
    for jdt in SLAB_DTYPES.values():
        if dt == jnp.dtype(jdt):
            return jdt
    raise ValueError(f"slab_dtype must be one of "
                     f"{sorted(SLAB_DTYPES)}, got {name!r}")


class SlabCodec:
    """Cached pytree ⇄ slab codec for one (treedef, shapes, dtypes,
    slab_dtype).

    The codec carries the **per-leaf dtype map**: ``encode`` casts each
    leaf to the declared aggregation dtype (``slab_dtype``), ``decode``
    restores every leaf's original dtype exactly — a bf16 leaf comes
    back bf16 even off a float32 slab and vice versa.  ``encode``/
    ``decode`` are jitted; both return fresh buffers (decode never
    returns views into the slab, so decoded trees survive the slab's
    donation into a later flush).
    """

    def __init__(self, treedef, shapes: Tuple[Tuple[int, ...], ...],
                 dtypes: Tuple[Any, ...], slab_dtype: str = "f32",
                 paths: Optional[Tuple[str, ...]] = None):
        if paths is None:
            paths = tuple(f"leaf[{i}]" for i in range(len(shapes)))
        for path, dt in zip(paths, dtypes):
            if not jnp.issubdtype(dt, jnp.floating):
                raise TypeError(
                    f"slab codec requires floating leaves, got {dt} "
                    f"at {path} (the slab is a floating array; integer "
                    "leaves would round-trip lossily)")
            if jnp.dtype(dt).itemsize > 4:
                raise TypeError(
                    f"slab codec requires leaves <= 32-bit, got {dt} "
                    f"at {path} (wider floats would be silently "
                    "quantized on the round trip)")
        self.treedef = treedef
        self.shapes = shapes
        self.dtypes = dtypes
        self.paths = paths
        self.slab_dtype = jnp.dtype(resolve_slab_dtype(slab_dtype))
        self.slab_dtype_name = "f32" \
            if self.slab_dtype == jnp.dtype(jnp.float32) else "bf16"
        self.sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
        self.offsets = tuple(int(o) for o in
                             np.cumsum((0,) + self.sizes)[:-1])
        self.size = int(sum(self.sizes))            # live elements P
        assert self.size > 0, "empty pytree has no slab"
        self.padded_size = -(-self.size // TILE_P) * TILE_P
        self._encode = jax.jit(self._encode_impl)
        self._decode = jax.jit(self._decode_impl)
        # the aggregator's master accumulator form: always float32,
        # whatever the wire/staging dtype.  For f32 codecs this IS the
        # encode executable (shared jit cache — zero extra compiles on
        # the historical path)
        if self.slab_dtype == jnp.dtype(jnp.float32):
            self._encode_master = self._encode
        else:
            self._encode_master = jax.jit(
                lambda tree: self._encode_as(tree, jnp.float32))

    # ------------------------------------------------------------ codec
    def _encode_as(self, tree, dtype):
        leaves = jax.tree_util.tree_leaves(tree)
        flat = jnp.concatenate(
            [jnp.ravel(x).astype(dtype) for x in leaves])
        return jnp.pad(flat, (0, self.padded_size - self.size))

    def _encode_impl(self, tree):
        return self._encode_as(tree, self.slab_dtype)

    def _decode_impl(self, slab):
        # the barrier pins slice-then-reshape: without it XLA hoists a
        # leaf's reshape above its slice, reshaping the WHOLE slab to
        # the leaf's minor dims — for an (.., 4, 2) leaf the TPU tiling
        # pads that copy 64x, which at xlstm-350m's P is 56 GB of HBM
        leaves = [
            jax.lax.optimization_barrier(slab[off:off + n])
            .reshape(shape).astype(dtype)
            for off, n, shape, dtype in zip(self.offsets, self.sizes,
                                            self.shapes, self.dtypes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def encode(self, tree) -> jax.Array:
        """tree -> (P_pad,) slab in the aggregation dtype (fresh
        buffer)."""
        return self._encode(tree)

    def encode_master(self, tree) -> jax.Array:
        """tree -> (P_pad,) **float32** slab — the aggregator's master
        params form, precision-independent of ``slab_dtype``."""
        return self._encode_master(tree)

    def decode(self, slab) -> Any:
        """(P_pad,) slab (any slab dtype) -> tree with the template's
        shapes and original per-leaf dtypes."""
        return self._decode(slab)

    def decode_host(self, slab) -> Any:
        """Decode + copy to host numpy — the snapshot/checkpoint form
        (valid forever, regardless of later donations)."""
        return jax.device_get(self._decode(slab))

    def __repr__(self):
        return (f"SlabCodec(leaves={len(self.sizes)}, P={self.size}, "
                f"padded={self.padded_size}, "
                f"dtype={self.slab_dtype_name})")


_CODEC_CACHE: Dict[Tuple, SlabCodec] = {}


def slab_codec(tree, slab_dtype: str = "f32") -> SlabCodec:
    """The cached codec for ``tree``'s structure (treedef + leaf shapes
    + dtypes) at the given aggregation dtype.  Two pytrees with
    identical structure share one codec — and therefore its compiled
    encode/decode executables."""
    flat_paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [x for _, x in flat_paths]
    paths = tuple(jax.tree_util.keystr(p) or f"leaf[{i}]"
                  for i, (p, _) in enumerate(flat_paths))
    shapes = tuple(tuple(np.shape(x)) for x in leaves)
    dtypes = tuple(jnp.dtype(getattr(x, "dtype", None)
                             or jnp.result_type(x)) for x in leaves)
    sdt = jnp.dtype(resolve_slab_dtype(slab_dtype))
    key = (treedef, shapes, dtypes, sdt)
    codec = _CODEC_CACHE.get(key)
    if codec is None:
        codec = _CODEC_CACHE[key] = SlabCodec(treedef, shapes, dtypes,
                                              slab_dtype=str(sdt),
                                              paths=paths)
    return codec


_SHARD_AUTO_MIN = 1 << 22     # elements: auto-shard only for multi-
#                               million-parameter slabs (below this the
#                               chunking overhead buys nothing)


def _auto_shards(padded_size: int) -> int:
    """Default shard count: 1 (the historical single-buffer path)
    unless the slab is multi-million-parameter AND the host has several
    local devices to spread the chunks across."""
    ndev = jax.local_device_count()
    if ndev <= 1 or padded_size < _SHARD_AUTO_MIN:
        return 1
    return min(ndev, padded_size // TILE_P)


def shard_chunks(padded_size: int, shards: int) -> Tuple[int, ...]:
    """Split ``padded_size`` (a TILE_P multiple) into ``shards``
    tile-aligned chunk lengths (descending by at most one tile)."""
    tiles = padded_size // TILE_P
    shards = max(1, min(int(shards), tiles))
    base, extra = divmod(tiles, shards)
    return tuple((base + (1 if i < extra else 0)) * TILE_P
                 for i in range(shards))


class SlabAggregator:
    """Params slab + ``K_max`` staging slots + the **one** donated fused
    flush executable (per chunk shape).

    The flush computes, for the first ``k`` staged rows ``g_i`` with
    weights ``w_i`` (zero-padded to ``K_max``)::

        params <- params - scale * (Σ_i w_i · g_i) / (Σ_i w_i)

    in place (the params slab is donated), and returns a fresh
    *published* copy of the new params that is safe to hand to workers:
    it never aliases the donated buffer (guarded by a regression test in
    ``tests/test_slab.py``).  One executable serves every buffer size
    ``1 <= k <= K_max`` purely through zero-weight masking: the flush
    always takes ``K_max`` rows, and an empty slot passes slot 0's row
    at weight 0.  The jit cache is per-aggregator, so
    ``flush_cache_size()`` is an exact probe that no per-K
    recompilation crept back in.

    **Staging by reference**: a slot holds a ``(P_pad,)`` row, never a
    slice of a matrix.  :meth:`stage` keeps the caller's slab itself
    when it is already a device array in the staging dtype on the
    staging device (a worker's fresh gradient output, in process): no
    executable runs and nothing is copied.  Anything else takes the one
    transfer or cast that staging needs.  A flush drops the rows it
    consumed, so staging memory is the rows held between flushes.

    **Mixed precision**: staging rows and the published slab are in the
    codec's ``slab_dtype``; the master params slab is always float32 and
    the reduction runs in float32 (bf16 rows are upcast inside the
    executable).  With the default f32 codec every cast is a trace-time
    no-op and the path is bit-for-bit the historical one.

    **Sharding**: ``shards > 1`` splits each staged row (and the master
    slab) along P into tile-aligned chunks placed round-robin across
    local devices — multi-million-parameter slabs stage across the host
    topology instead of one device.  Chunking never changes the math:
    the masked fold is elementwise along P, so the sharded flush is
    bitwise identical to the unsharded one.  ``shards=None`` picks
    automatically (1 unless the slab is huge and devices are plural).

    **Slab-resident optimizer**: with ``optimizer=``
    :class:`repro.optim.SlabOptimizer` the update step lives here too —
    momentum's ``mu`` / AdamW's ``mu``/``nu`` moments are **f32** slabs
    shaped and sharded exactly like the master params (f32 even under a
    bf16 codec), donated into ONE fused flush+optimizer executable per
    chunk shape, with AdamW's bias correction driven by the int32
    update count carried in state (the convention shared with the
    pytree-form optimizers).  ``optimizer="sgd"`` (the default) keeps
    the historical executable untouched, bit for bit.
    """

    def __init__(self, codec: SlabCodec, params, k_max: int, *,
                 use_pallas: Optional[bool] = None,
                 interpret: bool = False,
                 shards: Optional[int] = None,
                 optimizer: Optional[SlabOptimizer] = None):
        assert k_max >= 1, k_max
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.codec = codec
        self.k_max = int(k_max)
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.opt = optimizer or SlabOptimizer("sgd")
        if shards is None:
            shards = _auto_shards(codec.padded_size)
        self.chunk_sizes = shard_chunks(codec.padded_size, shards)
        self.shards = len(self.chunk_sizes)
        self.chunk_offsets = tuple(int(o) for o in
                                   np.cumsum((0,) + self.chunk_sizes)[:-1])
        self._devices = jax.local_devices()
        self._flush = jax.jit(self._flush_impl, donate_argnums=(0,))
        # the fused flush+optimizer executables: the params slab AND the
        # moment slabs are donated — updated in place, never escaping.
        # The unit-lr pytree (init, update) pair supplies the jnp-path
        # math, so slab-form and pytree-form share one convention
        self._pair = self.opt.pair()
        if self.opt.name == "momentum":
            self._flush_opt = jax.jit(self._flush_momentum_impl,
                                      donate_argnums=(0, 1))
        elif self.opt.name == "adamw":
            self._flush_opt = jax.jit(self._flush_adamw_impl,
                                      donate_argnums=(0, 1, 2))
        else:
            self._flush_opt = None
        if self.shards == 1:
            # historical single-device path, bit for bit
            self._slab = codec.encode_master(params)
        else:
            self._slab = self._shard(codec.encode_master(params))
        # staging slots: None, or one staged row as a tuple of its
        # per-chunk (n,) arrays
        self._rows: List[Optional[Tuple[jax.Array, ...]]] = \
            [None] * self.k_max
        # published params slab: always a fresh executable output
        self._pub = codec.encode(params)
        self._init_opt_state()

    def _init_opt_state(self) -> None:
        """Zero the optimizer state: **f32** moment slabs shaped (and
        sharded) exactly like the master params slab — f32 even under a
        bf16 codec, per the moments-never-narrow rule — plus the int32
        update count."""
        self._count = jnp.zeros((), jnp.int32)
        self._moments: Dict[str, Any] = {}
        for name in self.opt.moment_names:
            if self.shards == 1:
                self._moments[name] = jnp.zeros(
                    (self.codec.padded_size,), jnp.float32)
            else:
                self._moments[name] = [
                    jax.device_put(jnp.zeros((n,), jnp.float32), d)
                    for n, d in zip(self.chunk_sizes,
                                    self._chunk_devices())]

    # ------------------------------------------------------ executables
    def _flush_impl(self, pslab, rows, weights, scale):
        # ``rows`` are the K_max staged rows, each its own buffer; both
        # branches reduce via zero-weight masking — slots past the live
        # count hold weight 0 and contribute exactly +0.0 — which is
        # what lets ONE executable serve every buffer size k.  The
        # reduction always runs in float32: the kernel upcasts bf16 rows
        # per tile, the jnp fold upcasts row by row (for f32 rows the
        # cast disappears at trace time)
        with jax.named_scope("aggregate"):
            if self.use_pallas:
                agg = flush_pallas(rows, weights,
                                   out_dtype=jnp.float32,
                                   interpret=self.interpret)
            else:
                agg = self._fold(rows, weights)
        with jax.named_scope("apply"):
            new = pslab - scale * (agg / jnp.sum(weights))
        # the second output is the published copy: a fresh buffer that
        # does NOT alias the donated input (tests/test_slab.py guards
        # this against XLA deciding to alias the two outputs)
        return new, self._published(new)

    def _published(self, new):
        """The publish copy of a freshly updated master slab: a fresh
        buffer that never aliases the donated master (in bf16 mode the
        publish IS the narrowing cast)."""
        with jax.named_scope("publish_cast"):
            if self.codec.slab_dtype == jnp.dtype(jnp.float32):
                return new + 0.0
            return new.astype(self.codec.slab_dtype)

    @staticmethod
    def _fold(rows, weights):
        """The jnp fallback's masked f32 fold, statically unrolled in
        slot order — structurally identical to the legacy per-leaf fold
        (same muls, same adds, same order), which keeps the sync round
        mean bitwise-equal to the pre-slab server.  (A fori_loop over
        only the k live rows compiles to different FMA contraction and
        drifts by 1 ulp.)"""
        agg = weights[0] * rows[0].astype(jnp.float32)
        for i in range(1, len(rows)):
            agg = agg + weights[i] * rows[i].astype(jnp.float32)
        return agg

    def _flush_momentum_impl(self, pslab, mu, count, rows, weights,
                             scale):
        # fused aggregate + heavy-ball momentum:  mu' = β·mu + ĝ ;
        # params' = params - scale·mu'.  ``pslab`` and ``mu`` are
        # donated; the moments stay f32 whatever the staging dtype
        if self.use_pallas:
            with jax.named_scope("aggregate"):
                upd, mu_new = flush_momentum_pallas(
                    rows, weights / jnp.sum(weights), mu,
                    self.opt.beta1, out_dtype=jnp.float32,
                    interpret=self.interpret)
            with jax.named_scope("apply"):
                new = pslab - scale * upd
                count_new = count + 1
        else:
            with jax.named_scope("aggregate"):
                g = self._fold(rows, weights) / jnp.sum(weights)
            with jax.named_scope("apply"):
                upd, st = self._pair.update(
                    g, {"count": count, "mu": mu}, pslab)
                new = pslab + scale * upd
                mu_new, count_new = st["mu"], st["count"]
        return new, mu_new, count_new, self._published(new)

    def _flush_adamw_impl(self, pslab, mu, nu, count, rows, weights,
                          scale):
        # fused aggregate + AdamW with bias correction off the int32
        # count carried in state (the shared step-count convention of
        # repro.optim).  ``pslab``/``mu``/``nu`` are donated
        if self.use_pallas:
            # one kernel aggregates and applies: it is named for the
            # update it emits
            with jax.named_scope("apply"):
                c = count + 1
                bc1, bc2 = bias_correction(c, self.opt.beta1,
                                           self.opt.beta2)
                new, mu_new, nu_new = flush_adamw_pallas(
                    rows, weights / jnp.sum(weights), pslab, mu, nu,
                    bc1, bc2, scale, b1=self.opt.beta1,
                    b2=self.opt.beta2, eps=self.opt.eps,
                    weight_decay=self.opt.weight_decay,
                    interpret=self.interpret)
                count_new = c
        else:
            with jax.named_scope("aggregate"):
                g = self._fold(rows, weights) / jnp.sum(weights)
            with jax.named_scope("apply"):
                upd, st = self._pair.update(
                    g, {"count": count, "mu": mu, "nu": nu}, pslab)
                new = pslab + scale * upd
                mu_new, nu_new = st["mu"], st["nu"]
                count_new = st["count"]
        return new, mu_new, nu_new, count_new, self._published(new)

    # ----------------------------------------------------------- chunks
    def _chunk_devices(self):
        return tuple(self._devices[i % len(self._devices)]
                     for i in range(self.shards))

    def _shard(self, slab) -> List[jax.Array]:
        """Split a full slab into device-placed chunks."""
        return [jax.device_put(slab[off:off + n], d)
                for off, n, d in zip(self.chunk_offsets, self.chunk_sizes,
                                     self._chunk_devices())]

    def _assemble(self, chunks) -> jax.Array:
        """Concatenate published chunks back into one wire-able slab."""
        return jnp.concatenate(
            [jax.device_put(c, self._devices[0]) for c in chunks])

    def _flush_chunk(self, slab, moments, rows, w, s):
        """One chunk's flush: (new master, new moments, new update
        count, published chunk).  SGD runs the historical executable,
        with no optimizer state in its arguments."""
        if self.opt.name == "sgd":
            new, pub = self._flush(slab, rows, w, s)
            return new, [], self._count, pub
        new, *state, count, pub = self._flush_opt(
            slab, *moments, self._count, rows, w, s)
        return new, state, count, pub

    # ------------------------------------------------------------- API
    def stage(self, slab, slot: int) -> bool:
        """Hold one ``(P_pad,)`` gradient slab in staging slot ``slot``.

        Returns True when the slab itself is held: it is a device array
        in the staging dtype on the staging device, so nothing runs and
        nothing is copied.  Otherwise returns False after the one
        transfer (a host row, a row on another device, each chunk of a
        sharded slab) or cast (a row in another dtype) staging needs.
        Nothing the aggregator runs writes a held slab."""
        assert 0 <= slot < self.k_max, (slot, self.k_max)
        if np.shape(slab) != (self.codec.padded_size,):
            raise ValueError(f"a gradient slab of shape {np.shape(slab)} "
                             f"cannot stage into ({self.codec.padded_size},)"
                             " rows")
        sdt = self.codec.slab_dtype
        chunks = [self._slab] if self.shards == 1 else self._slab
        devices = [next(iter(c.devices())) for c in chunks]
        if (self.shards == 1 and isinstance(slab, jax.Array)
                and slab.dtype == sdt
                and slab.sharding.device_set == {devices[0]}):
            self._rows[slot] = (slab,)
            return True
        rows = []
        for off, n, d in zip(self.chunk_offsets, self.chunk_sizes,
                             devices):
            row = jax.device_put(
                slab if self.shards == 1 else slab[off:off + n], d)
            rows.append(row if row.dtype == sdt else row.astype(sdt))
        self._rows[slot] = tuple(rows)
        return False

    def flush_apply(self, weights: np.ndarray, scale: float) -> jax.Array:
        """Aggregate the first ``len(weights)`` staged rows, apply the
        update and drop every staged row.  Returns the freshly published
        params slab."""
        k = len(weights)
        assert 1 <= k <= self.k_max, (k, self.k_max)
        live = self._rows[:k]
        assert all(r is not None for r in live), \
            f"flush of {k} rows with an empty slot among them"
        # every empty slot passes slot 0's row at weight 0: the
        # executable's K_max operands are live rows, none kept for it
        slots = live + [live[0]] * (self.k_max - k)
        self._rows = [None] * self.k_max
        wfull = np.zeros((self.k_max,), np.float32)
        wfull[:k] = np.asarray(weights, np.float32)
        w = jnp.asarray(wfull)
        s = jnp.asarray(scale, jnp.float32)
        names = self.opt.moment_names
        if self.shards == 1:
            self._slab, moments, self._count, self._pub = \
                self._flush_chunk(self._slab,
                                  [self._moments[n] for n in names],
                                  tuple(r[0] for r in slots), w, s)
            self._moments = dict(zip(names, moments))
            return self._pub
        pubs, count = [], self._count
        for i in range(self.shards):
            self._slab[i], moments, count, pub = self._flush_chunk(
                self._slab[i], [self._moments[n][i] for n in names],
                tuple(r[i] for r in slots), w, s)
            for n, m in zip(names, moments):
                self._moments[n][i] = m
            pubs.append(pub)
        self._count = count
        self._pub = self._assemble(pubs)
        return self._pub

    @property
    def params_slab(self) -> jax.Array:
        """The published params slab (safe to ship / hold)."""
        return self._pub

    def params_tree(self):
        """Decode the published params into a fresh pytree."""
        return self.codec.decode(self._pub)

    def params_tree_host(self):
        """Decode + host copy — the checkpoint/snapshot form."""
        return self.codec.decode_host(self._pub)

    def reset_params(self, params) -> None:
        """Replace the live params (checkpoint restore)."""
        master = self.codec.encode_master(params)
        self._slab = master if self.shards == 1 else self._shard(master)
        self._pub = self.codec.encode(params)

    def reset_opt_state(self, state: Optional[Dict[str, Any]] = None
                        ) -> None:
        """Resync the optimizer state (checkpoint restore): ``None``
        zeros the moments and the update count; a dict (the
        :meth:`opt_state_host` form — f32 ``(P_pad,)`` arrays per moment
        name plus an int ``count``) reloads them, re-sharding along P
        exactly like the master slab."""
        if state is None:
            self._init_opt_state()
            return
        missing = [n for n in self.opt.moment_names if n not in state]
        if missing:
            raise ValueError(
                f"optimizer state is missing moment slab(s) {missing} "
                f"for {self.opt.name!r} — the checkpoint was written by "
                "a run with a different optimizer")
        self._count = jnp.asarray(int(state["count"]), jnp.int32)
        self._moments = {}
        for name in self.opt.moment_names:
            full = jnp.asarray(np.asarray(state[name], np.float32))
            assert full.shape == (self.codec.padded_size,), \
                (name, full.shape, self.codec.padded_size)
            self._moments[name] = full if self.shards == 1 \
                else self._shard(full)

    def opt_state_host(self) -> Optional[Dict[str, Any]]:
        """Host copies of the moment slabs + the int update count (the
        checkpoint form), or ``None`` for plain SGD.  Per the donation
        rules this must run under the owner's lock: the moments are
        donated buffers, and a concurrent flush would invalidate them
        mid-copy."""
        if self.opt.name == "sgd":
            return None
        out: Dict[str, Any] = {}
        for name in self.opt.moment_names:
            m = self._moments[name]
            slab = m if self.shards == 1 else self._assemble(m)
            out[name] = np.asarray(jax.device_get(slab), np.float32)
        out["count"] = int(jax.device_get(self._count))
        return out

    def wipe_staging(self) -> None:
        """Drop every staged row unconsumed.  A discarded row is gone,
        not masked: it may be non-finite (a diverged gradient the
        restore is recovering from), and no later flush can meet it with
        a zero weight (``0 · inf = nan``)."""
        self._rows = [None] * self.k_max

    def warmup(self) -> None:
        """Compile the flush executable before the clock starts (one
        compile per chunk shape, for any fleet size — vs the pre-slab
        server's one compile per K in 1..num_workers).  The warmup flush
        uses scale=0 over a zero row, so the params are bitwise
        unchanged."""
        self.stage(jnp.zeros((self.codec.padded_size,),
                             self.codec.slab_dtype), 0)
        self.flush_apply(np.ones((1,), np.float32), 0.0)
        # a zero-gradient scale-0 flush leaves params AND moments
        # bitwise unchanged, but it does tick the update count — rewind
        # it so training starts at step 0 with warm executables
        if self.opt.name != "sgd":
            self._count = jnp.zeros((), jnp.int32)

    def grow(self, k_max: int) -> None:
        """Lengthen the staging slots to ``k_max`` (elastic fleet
        admission).  Already-staged rows stay in their slots — a hybrid
        buffer keeps gradients staged *between* flushes, so growth
        mid-buffer must not lose them.  No warmup flush runs here (it
        would fold staged row 0 into the params); the next real flush
        traces the new operand count, so growth costs one compile per
        resize — paid only by elastic fleets, never by a fixed one.
        Shrinking is never done: a departed worker's slot just stays
        empty."""
        k_max = int(k_max)
        if k_max <= self.k_max:
            return
        self._rows += [None] * (k_max - self.k_max)
        self.k_max = k_max

    def flush_cache_size(self) -> int:
        """Number of compiled flush executables (the probe asserted to
        be exactly 1 in tests for the unsharded default, regardless of
        fleet size / K — growth via :meth:`grow` adds one entry per
        resize, and sharded slabs hold one entry per distinct chunk
        shape).  With a moment-carrying optimizer the probe covers the
        fused flush+optimizer executable instead — still exactly one
        per buffer shape."""
        fn = self._flush if self.opt.name == "sgd" else self._flush_opt
        return int(fn._cache_size())


class SlabBuffer:
    """Slab-backed gradient buffer: the staged-rows counterpart of
    :class:`repro.core.buffer.GradientBuffer`.

    Gradient slabs are staged into the aggregator as they arrive (row =
    arrival order); only the parameter versions they were computed
    against are tracked host-side, for the staleness weights.  The
    flush itself is :meth:`SlabAggregator.flush_apply`.
    """

    def __init__(self, aggregator: SlabAggregator,
                 staleness_decay: float = 1.0):
        self.agg = aggregator
        self.staleness_decay = float(staleness_decay)
        self._versions: List[int] = []

    def __len__(self) -> int:
        return len(self._versions)

    def add(self, slab, version: int) -> bool:
        """Stage ``slab`` in the next slot; True when the slab itself is
        held (:meth:`SlabAggregator.stage`)."""
        held = self.agg.stage(slab, len(self._versions))
        self._versions.append(int(version))
        return held

    def weights(self, current_version: int) -> np.ndarray:
        """Staleness weights ``decay^(now - v_i)`` for the staged rows.
        The exponent is clamped at 0: after a checkpoint restore rolls
        the version back, an in-flight gradient can be tagged with a
        *future* version, and a negative exponent would upweight exactly
        the abandoned-history gradients the restore discards."""
        stale = np.maximum(0.0, current_version
                           - np.asarray(self._versions, np.float64))
        return self.staleness_decay ** stale

    def clear(self) -> None:
        """Forget rows that a flush just **consumed** (the flush has
        already dropped them from the aggregator)."""
        self._versions = []

    def discard(self) -> None:
        """Drop staged rows **unconsumed** (checkpoint restore).  The
        rows are dropped, not just masked: a discarded gradient may be
        non-finite — that divergence can be exactly what the restore is
        recovering from — and ``0 · inf = nan`` would defeat the
        masking on every later flush."""
        self.agg.wipe_staging()
        self._versions = []
