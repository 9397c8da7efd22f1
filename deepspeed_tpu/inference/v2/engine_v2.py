"""RaggedInferenceEngineV2 — the FastGen-style serving engine.

Reference: ``deepspeed/inference/v2/engine_v2.py`` [K] —
``InferenceEngineV2.put(uids, tokens)`` over a ragged batch with blocked KV
cache and Dynamic SplitFuse scheduling (SURVEY §2.5 row "Inference v2").

TPU-first: instead of ragged kernels over dynamic shapes, the engine
compiles a small number of fixed-shape programs and reuses them for any
request mix (XLA traces once; raggedness lives in int32 metadata):

* ``prefill_batch`` — ``chunk`` prompt tokens for each of up to
  ``prefill_batch`` sequences at once, writing KV pages through each row's
  block table (Dynamic SplitFuse = long prompts become several chunk calls
  interleaved with decodes; round 3 batches the chunks across sequences).
* ``decode_burst``  — ``k`` successive decode steps for all
  ``max_batch_slots`` sequences in ONE device program: sampling happens
  in-graph (greedy or temperature) and only ``[k, B]`` int32 token ids
  return to the host — no per-token logits round-trip.
  Page tables are fully reserved at admission (prompt + generation budget),
  so a burst never needs host page allocation mid-flight.

Architecture deltas (norms, positions, FFN, head) live in
``adapters.ModelAdapterV2`` — llama/mistral/mixtral AND OPT serve on the
same engine (reference keeps per-arch model implementations under
``inference/v2/model_implementations`` [K]).

Both programs donate the pool and carry it WHOLE through their layer
scan, addressed by ``(layer, page)``: a step's rows (decode) or pages
(prefill) are scattered into it in place, and attention reads pages
``l·N + page`` of its flat view.  No program forms a layer's ``pool[l]``
(see ``_layer_step`` for why), so KV updates are in-place in HBM and no
call moves more of the cache than it reads or writes.

Prefill cost is O(pages allocated so far), not O(max_seq_len): each
chunk call gathers/masks only ``kb`` pages per row, where ``kb`` is the
smallest power-of-two page bucket covering the batch's deepest
``start_pos + chunk`` (VERDICT r3 item 6 — the round-2 "O(max_seq_len)
per chunk" cost note is gone).  Buckets are static shapes, so at most
``log2(max_blocks/chunk_blocks)+1`` prefill programs ever compile.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.pallas.paged_attention import (paged_decode_attention,
                                           paged_decode_impl)
from ...telemetry.perf import get_compile_tracker, tracked_jit
from ...utils.logging import log_dist
from .adapters import ModelAdapterV2, make_adapter
from .kv_cache import KVCacheConfig, init_kv_pool
from .scheduler import RaggedScheduler, Request


class _null_ctx:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return None


def _sample(logits: jnp.ndarray, temperature: jnp.ndarray,
            key: jax.Array) -> jnp.ndarray:
    """In-graph sampling over ``[N, V]`` fp32 logits: greedy when
    ``temperature <= 0``, else softmax sampling at that temperature."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    drawn = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, drawn, greedy)


class RaggedInferenceEngineV2:
    def __init__(self, model: Any, params: Any,
                 cache_config: Optional[KVCacheConfig] = None,
                 max_batch_slots: int = 8, prefill_chunk: int = 128,
                 prefill_batch: int = 2, decode_burst: int = 8,
                 adapter: Optional[ModelAdapterV2] = None,
                 mesh: Any = None,
                 scheduler_factory: Optional[Callable] = None,
                 ledger_key: str = "inference_v2/kv_pool",
                 moe_telemetry: bool = True):
        self.model = model
        self.adapter = adapter or make_adapter(model)
        self.config = model.config
        self.params = params
        self.cache_config = cache_config or KVCacheConfig()
        #: TP-sharded serving (reference v2 serves TP-sharded models):
        #: params land in their ``param_specs`` shardings, the KV pool is
        #: sharded on the kv-head dim over the ``tensor`` axis, and the
        #: compiled programs run under GSPMD.  Decode attention runs the
        #: PAGED PALLAS KERNEL per TP shard through an explicit shard_map
        #: over the kv-head axis (paged_decode_attention_tp) — heads are
        #: independent, so no cross-rank communication.
        self.mesh = mesh
        self.last_attn_path = None  # set at trace time by attend_fn
        self._tp = int(mesh.shape.get("tensor", 1)) if mesh is not None else 1
        if self._tp > 1 and self.adapter.kv_heads % self._tp:
            raise ValueError(
                f"tensor axis {self._tp} must divide kv heads "
                f"{self.adapter.kv_heads} for TP serving")
        if prefill_chunk % self.cache_config.block_size:
            raise ValueError("prefill_chunk must be a multiple of block_size")
        #: Mistral-style window, threaded into both compiled programs'
        #: masks (pages before the window still occupy pool slots — a
        #: window-aware page-release policy is a later optimization)
        self.window = self.adapter.window
        if self.cache_config.max_seq_len % prefill_chunk:
            # keeps every chunk's page-table slice in range: dynamic_slice
            # clamps out-of-bounds starts, which would silently retarget a
            # chunk's KV writes onto the sequence's EARLIER pages
            raise ValueError("max_seq_len must be a multiple of prefill_chunk")
        #: the serving plane swaps in its prefix-sharing scheduler here —
        #: same planner surface, refcounted page reservations
        make_sched = scheduler_factory or RaggedScheduler
        self.scheduler = make_sched(self.cache_config, max_batch_slots,
                                    prefill_chunk, prefill_batch)
        if self._tp > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            from ...parallel.mesh import strip_manual_axes

            spec_tree = self.model.param_specs(params)
            self.params = jax.tree.map(
                lambda p, s: jax.device_put(
                    p, NamedSharding(mesh, strip_manual_axes(*s))),
                params, spec_tree)
            # allocate the pool DIRECTLY into its sharding — a serving
            # config sizes the pool near HBM capacity, so transiently
            # materializing it replicated would OOM at startup
            pool_sharding = NamedSharding(
                mesh, PartitionSpec(None, None, None, "tensor", None))
            ad, cc = self.adapter, self.cache_config
            self.pool = tracked_jit(
                lambda: init_kv_pool(ad, cc), "inference_v2/pool_init",
                tracker=get_compile_tracker(),
                out_shardings={"k": pool_sharding, "v": pool_sharding})()
        else:
            self.pool = init_kv_pool(self.adapter, self.cache_config)
        from ...telemetry.memory import get_memory_ledger

        _mem = get_memory_ledger()
        if _mem.enabled:
            # the paged KV pool is the serving plane's dominant HBM
            # allocation — register it so `mem show` and OOM forensics
            # name it instead of reporting one giant untracked array
            # ledger_key is per-instance so multi-replica serving gets
            # DISTINCT kv_cache sub-keys (same key would silently replace)
            _mem.register_tree(
                "kv_cache", ledger_key, self.pool,
                tag=f"paged KV pool ({self.cache_config.num_blocks} x "
                    f"{self.cache_config.block_size} tokens)")
        self.max_slots = max_batch_slots
        self.chunk = prefill_chunk
        self.prefill_batch = max(1, prefill_batch)
        self.decode_burst = max(1, decode_burst)
        self._prefill = tracked_jit(self._prefill_batch_fn,
                                    "inference_v2/prefill",
                                    tracker=get_compile_tracker(),
                                    donate_argnums=(1,),
                                    static_argnames=("kb",))
        self._decode_jits: Dict[int, Callable] = {}
        self._key = jax.random.PRNGKey(0)
        #: MoE serving telemetry (ISSUE 19): when the model routes through
        #: a MOELayer, the decode program additionally returns the gate's
        #: per-expert load so the router/autoscaler can see hot experts.
        #: One persistent moe-only collector is active at trace time; the
        #: stats ride the program's output pytree ([L, E] load fractions
        #: averaged over the burst), so cached calls pay one tiny extra
        #: device→host transfer and zero recompiles.
        from ...telemetry import numerics

        self._moe_coll = (
            numerics.Collector(probes=False, moe=True, tag="serving")
            if moe_telemetry
            and getattr(model, "_moe_layer", None) is not None else None)
        #: host-side rolling per-expert load (fractions, sum≈1) and the
        #: derived max/mean imbalance — the router's placement signal
        self.last_moe_stats: Optional[Dict[str, Any]] = None
        #: per program ("prefill", "decode"): (entry name, width) of each
        #: column block of the stats it packs, written as it is traced
        self._moe_columns: Dict[str, List[Tuple[str, int]]] = {}
        log_dist(f"inference v2: pool={self.cache_config.num_blocks}"
                 f"x{self.cache_config.block_size} tokens, "
                 f"slots={max_batch_slots}, chunk={prefill_chunk}"
                 f"x{prefill_batch}, burst={self.decode_burst}, "
                 f"adapter={type(self.adapter).__name__}")

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------

    def _layer_step(self, params, lp, l, pool, x_flat, positions_flat,
                    write_fn, attend_fn):
        """Shared per-layer skeleton: qkv → KV write → attention →
        post-attn block.  ``write_fn``/``attend_fn`` differ between the
        prefill and decode programs.

        ``pool`` is the WHOLE pool ``{"k", "v"}: [L, N, bs, kv_h, d]``, a
        carry of the layer scan, and ``l`` this layer's index: the write
        scatters rows or pages at ``(l, page)`` and attention reads pages
        ``l·N + page`` of the flat view (:meth:`_flat_pool`).  No layer's
        ``pool[l]`` is ever formed: a slice of a scanned stack handed to
        a custom call (the paged kernel) is copied out and the updated
        layer copied back, which was 62% of the serving cell's device
        time (PERF.md §6, PRs 25, 27 and 28).  Write, then attend: only
        the written pool lives on, so the write stays in place."""
        ad = self.adapter
        q, kk, vv = ad.qkv(lp, x_flat, positions_flat)
        pool = write_fn(pool, l, kk, vv)
        attn = attend_fn(q, pool, l)
        x_flat = ad.post_attn(lp, x_flat, attn, params, l)
        return x_flat, pool

    @staticmethod
    def _flat_pool(pool):
        """The pool as ``[L·N, bs, kv_h, d]``: layer ``l``'s page ``p`` is
        page ``l·N + p``.  Two adjacent major dims merged: a bitcast."""
        return {name: a.reshape((-1,) + a.shape[2:])
                for name, a in pool.items()}

    def _scan_layers(self, params, pool, x, positions_flat, write_fn,
                     attend_fn):
        """The layer scan of both programs.  Carry: the activations and
        the pool; ``xs``: what the adapter's ``layers(params)`` holds, a
        layer's slice a step, and the layer's index; ``ys``: the MoE
        gate's stats (``moe_stats`` inside ``model._ffn``), which must
        leave the scan as ``ys`` — names ride the dict keys.  A leaf in
        ``xs`` is sliced whatever is done with it later, so what an
        adapter wants whole it leaves out of ``layers()`` and reads from
        ``params`` at ``l``, both of which its hooks are given (as the
        pool is read at ``l`` here)."""
        from ...telemetry import numerics

        ad = self.adapter

        def layer(carry, xs):
            x, pool = carry
            lp, l = xs
            mark = numerics.scan_mark()
            x, pool = self._layer_step(params, lp, l, pool, x,
                                       positions_flat, write_fn, attend_fn)
            return (x, pool), numerics.scan_drain(mark)

        (x, pool), stats = jax.lax.scan(
            layer, (x, pool),
            (ad.layers(params), jnp.arange(ad.num_layers, dtype=jnp.int32)))
        numerics.scan_collect(stats)  # keep the per-layer axis
        return x, pool

    def _prefill_batch_fn(self, params, pool, tokens, tables, start_pos,
                          last_idx, temperature, key, *, kb):
        """Up to ``Bp`` sequences' chunks at once: ``tokens [Bp, C]`` at
        positions ``start_pos[r] + [0..C)``; rows beyond the live chunk
        count carry all-zero tables (page 0 = scratch).  ``kb`` (static)
        is the page bucket this program attends over — the first ``kb``
        pages of each row's table cover every key written so far, so the
        gather/mask is O(allocated), not O(max_seq_len).  Returns
        (sampled token ids ``[Bp]``, pool, the gate's stats packed or None)."""
        ad = self.adapter
        Bp, C = tokens.shape
        bs = self.cache_config.block_size
        mb = int(kb)  # attend over the bucket, not the full table width
        n_rep = ad.num_heads // ad.kv_heads
        positions = start_pos[:, None] + jnp.arange(C)[None, :]  # [Bp, C]
        pos_flat = positions.reshape(-1)
        x = ad.embed(params, tokens.reshape(-1), pos_flat)  # [Bp*C, H]
        page_cursor = start_pos // bs  # chunks & starts are page-aligned

        # per-row page slice for this chunk's writes: [Bp, C//bs]
        pages = jax.vmap(
            lambda row, cur: jax.lax.dynamic_slice(row, (cur,), (C // bs,))
        )(tables, page_cursor)
        pages_flat = pages.reshape(-1)

        from ...ops.masks import local_attention_mask

        karange = jnp.arange(mb * bs)
        mask = jax.vmap(lambda p: local_attention_mask(
            p, karange, causal=True, window=self.window))(positions)
        mask = mask[:, None]  # [Bp, 1(head), C, mb*bs]

        n_pages = self.cache_config.num_blocks
        page_shape = (Bp * (C // bs), bs, ad.kv_heads, ad.head_dim)

        def write_fn(pool, l, kk, vv):
            # whole pages, scattered at (l, page) into the carried pool
            return {"k": pool["k"].at[l, pages_flat].set(
                        kk.reshape(page_shape)),
                    "v": pool["v"].at[l, pages_flat].set(
                        vv.reshape(page_shape))}

        def attend_fn(q, pool, l):
            # gather only the bucket's pages (every key written so far
            # lives in the first kb pages of each row's table) and attend
            # chunk-queries over them — O(allocated), not O(max_seq_len).
            # One gather out of the carried buffer's flat view, never a
            # layer sliced out first
            flat = self._flat_pool(pool)
            idx = tables[:, :mb] + l * n_pages
            kf = flat["k"][idx].reshape(Bp, mb * bs, ad.kv_heads,
                                        ad.head_dim)
            vf = flat["v"][idx].reshape(Bp, mb * bs, ad.kv_heads,
                                        ad.head_dim)
            if n_rep > 1:
                kf = jnp.repeat(kf, n_rep, axis=2)
                vf = jnp.repeat(vf, n_rep, axis=2)
            qb = q.reshape(Bp, C, ad.num_heads, ad.head_dim)
            scale = 1.0 / np.sqrt(ad.head_dim)
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kf
                           ).astype(jnp.float32) * scale
            s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(ad.dtype)
            attn = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
            return attn.reshape(Bp * C, ad.num_heads, ad.head_dim)

        x, pool = self._scan_layers(params, pool, x, pos_flat, write_fn,
                                    attend_fn)
        x = ad.finalize(params, x).reshape(Bp, C, -1)
        last_h = jnp.take_along_axis(
            x, last_idx[:, None, None], axis=1)[:, 0]  # [Bp, H]
        logits = ad.logits(params, last_h)  # [Bp, V]
        return (_sample(logits, temperature, key), pool,
                self._pack_moe_stats("prefill"))

    def _decode_burst_fn(self, params, pool, tokens, kv_lens, tables,
                         max_pos, temperature, key, *, n_steps: int):
        """``n_steps`` decode iterations entirely on device: each step
        writes KV at ``kv_lens`` through ``tables``, attends via the paged
        kernel, samples the next token in-graph and feeds it back.  Write
        positions clamp at ``max_pos`` (a slot that hit EOS/budget inside
        the burst only scribbles its own reserved pages; the host discards
        its surplus tokens).  Returns (token ids ``[n_steps, B]``, pool,
        the gate's stats packed or None)."""
        from ...telemetry import numerics

        ad = self.adapter
        B = tokens.shape[0]
        bs = self.cache_config.block_size
        n_pages = self.cache_config.num_blocks

        def one_step(carry, key):
            tokens, kv_lens, pool = carry
            step_mark = numerics.scan_mark()
            wp = jnp.minimum(kv_lens, max_pos)  # [B] write positions
            page_ids = tables[jnp.arange(B), wp // bs]
            offsets = wp % bs
            x = ad.embed(params, tokens, wp)

            def write_fn(pool, l, kk, vv):
                # one scatter of [B, kv_h, d] rows at (l, page, offset)
                return {"k": pool["k"].at[l, page_ids, offsets].set(kk),
                        "v": pool["v"].at[l, page_ids, offsets].set(vv)}

            def attend_fn(q, pool, l):
                # the kernel fetches pages from HBM by page id: it gets
                # the whole pool's flat view, and the layer's offset is
                # folded into the tables it prefetches anyway
                flat = self._flat_pool(pool)
                layer_tables = tables + l * n_pages
                # what paged_decode_attention will run for these head
                # counts on this platform, by its own test
                impl = paged_decode_impl(ad.num_heads // self._tp,
                                         ad.kv_heads // self._tp)
                if self._tp > 1:
                    # the Pallas kernel runs PER TP SHARD via an explicit
                    # shard_map over the kv-head axis (heads independent,
                    # zero cross-rank comm)
                    from ...ops.pallas.paged_attention import (
                        paged_decode_attention_tp)

                    self.last_attn_path = f"{impl}_tp_shard_map"
                    return paged_decode_attention_tp(
                        q, flat["k"], flat["v"], layer_tables, wp + 1,
                        mesh=self.mesh, window=self.window)
                self.last_attn_path = impl
                return paged_decode_attention(
                    q, flat["k"], flat["v"], layer_tables, wp + 1,
                    window=self.window)

            x, pool = self._scan_layers(params, pool, x, wp, write_fn,
                                        attend_fn)
            x = ad.finalize(params, x)
            logits = ad.logits(params, x)  # [B, V]
            nxt = _sample(logits, temperature, key)
            step_stats = numerics.scan_drain(step_mark)
            return (nxt, kv_lens + 1, pool), (nxt, step_stats)

        keys = jax.random.split(key, n_steps)
        (_, _, pool), (toks, stats) = jax.lax.scan(
            one_step, (tokens, kv_lens, pool), keys)
        numerics.scan_collect(stats, combine=True)  # mean over the burst
        return toks, pool, self._pack_moe_stats("decode")

    def _decode(self, n_steps: int) -> Callable:
        fn = self._decode_jits.get(n_steps)
        if fn is None:
            fn = tracked_jit(functools.partial(self._decode_burst_fn,
                                               n_steps=n_steps),
                             "inference_v2/decode_burst",
                             tracker=get_compile_tracker(),
                             static_context={"n_steps": n_steps},
                             donate_argnums=(1,))
            self._decode_jits[n_steps] = fn
        return fn

    # ------------------------------------------------------------------
    # serving surface
    # ------------------------------------------------------------------

    def put(self, prompt: List[int], max_new_tokens: int = 32) -> Request:
        """Admit one request (reference ``engine.put`` role)."""
        return self.scheduler.add_request(prompt, max_new_tokens)

    # -- MoE serving telemetry -----------------------------------------

    def _pack_moe_stats(self, program: str) -> Optional[jnp.ndarray]:
        """Inside a program, after its layer scan: the active collector's
        entries (each with the per-layer axis the scan gave it: ``[L]`` or
        ``[L, E]``) as ONE float32 array ``[L, columns]``, so that the host
        fetches the router's stats in one transfer beside the tokens
        (fetched entry by entry they cost the serving round 7.5 ms of
        host, PERF.md PR 27).  Which columns hold what is a fact of the
        trace, kept for ``program`` in ``_moe_columns``."""
        from ...telemetry import numerics

        coll = numerics.active()
        named = coll.harvest() if coll is not None else None
        if not named:
            return None
        layers = self.adapter.num_layers
        blocks = [(key.partition(":")[2],
                   named[key].astype(jnp.float32).reshape(layers, -1))
                  for key in sorted(named)]
        self._moe_columns[program] = [(name, int(b.shape[1]))
                                      for name, b in blocks]
        return jnp.concatenate([b for _, b in blocks], axis=1)

    def _ingest_moe_stats(self, packed: np.ndarray, tel: Any, program: str,
                          steps: int = 1) -> None:
        """Host side of one call's gate stats.  Every call feeds the
        dropless layer's counters; a decode burst (``steps`` steps, the
        stats their mean) also sets what the router and autoscaler read:
        per-expert load gauges and the imbalance/drop scalars.  Telemetry
        must never kill a serving round: a layout that does not fit the
        array, or an entry the gate did not report, is skipped."""
        layout = self._moe_columns.get(program, ())
        if packed.ndim != 2 or packed.shape[1] != sum(w for _, w in layout):
            return
        cols, at = {}, 0
        for name, width in layout:
            cols[name] = packed[:, at:at + width]
            at += width
        if tel.enabled and "moe/experts_active" in cols \
                and "moe/assignments" in cols:
            # rows x k is the same in every layer; non-empty groups are not
            tel.inc_counter(
                "inference/moe/assignments",
                v=float(cols["moe/assignments"].mean()) * steps,
                help="token-to-expert assignments computed: rows x k, a "
                     "step of a call")
            tel.inc_counter(
                "inference/moe/experts_active",
                v=float(cols["moe/experts_active"].sum()) * steps,
                help="experts with at least one row (whose weights the "
                     "grouped matmul reads), summed over layers and steps")
        load = cols.get("moe/load")
        if program != "decode" or load is None:
            return
        load = load.astype(np.float64)                        # [L, E]
        mean = load.mean(axis=1)
        # max/mean of the hottest layer: 1.0 = a balanced router
        hottest = np.where(mean > 0, load.max(axis=1)
                           / np.maximum(mean, 1e-12), 0.0).max()
        drop = cols.get("moe/drop_rate")
        stats = {"load": load.mean(axis=0).tolist(),
                 "imbalance": float(hottest),
                 "drop_rate": float(drop.mean()) if drop is not None else 0.0}
        self.last_moe_stats = stats
        if not tel.enabled:
            return
        for e, frac in enumerate(stats["load"]):
            tel.set_gauge(f"inference/moe/expert_load_e{e}", float(frac),
                          help="per-expert token-load fraction of the "
                               "last decode burst (hot-expert signal)")
        tel.set_gauge("inference/moe/load_imbalance", stats["imbalance"],
                      help="max/mean expert load of the last decode "
                           "burst (1.0 = balanced router)")
        tel.set_gauge("inference/moe/drop_rate", stats["drop_rate"],
                      help="capacity-dropped token fraction of the last "
                           "decode burst")

    def _collecting_moe(self):
        """Around a program call: the collector only matters at trace time
        (the first call of a shape); cached calls just return the stats
        the traced program already threads out."""
        from ...telemetry import numerics

        return (numerics.collecting(self._moe_coll)
                if self._moe_coll is not None else _null_ctx())

    def moe_load_imbalance(self) -> float:
        """Router-facing hot-expert signal: max/mean expert load of the
        last decode burst (1.0 = balanced; 0.0 = no MoE data yet)."""
        if not self.last_moe_stats:
            return 0.0
        return float(self.last_moe_stats.get("imbalance", 0.0))

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def _prefill_bucket(self, chunks) -> int:
        """Static page-bucket for this prefill call: smallest power-of-two
        multiple of the chunk's page count that covers the deepest row's
        ``start_pos + chunk`` keys.  Bounded program count (log2 buckets),
        O(allocated) gather cost."""
        bs = self.cache_config.block_size
        mb = self.cache_config.max_blocks_per_seq
        need = max((ch.start_pos + self.chunk) // bs for ch in chunks)
        kb = max(self.chunk // bs, 1)
        while kb < need:
            kb *= 2
        return min(kb, mb)

    def step(self, temperature: float = 0.0,
             eos_token_id: Optional[int] = None,
             rng: Optional[np.random.Generator] = None) -> int:
        """One scheduler step: a batched prefill call and/or a decode
        burst.  While prefill work exists the burst length is 1 so
        SplitFuse keeps interleaving chunks with decodes; once all prompts
        are in, decodes run ``decode_burst`` steps per dispatch.  Returns
        the number of tokens processed."""
        del rng  # sampling is in-graph now; kept for API compat
        from ...telemetry import get_telemetry

        tel = get_telemetry()
        with tel.span("inference/step") as sp:
            with tel.span("inference/plan"):
                chunks, decode = self.scheduler.plan_step()
            sp.set(chunks=len(chunks), decoding=len(decode))
            temp = jnp.float32(temperature)
            n_tokens = 0
            if chunks:
                n_tokens += self._step_prefill(tel, chunks, temp,
                                               eos_token_id)
            if decode:
                n_tokens += self._step_decode(tel, chunks, decode, temp,
                                              eos_token_id)
        return n_tokens

    def _step_prefill(self, tel: Any, chunks, temp, eos_token_id) -> int:
        with tel.span("inference/pack", args={"kind": "prefill"}):
            Bp, C = self.prefill_batch, self.chunk
            tokens = np.zeros((Bp, C), np.int32)
            tables = np.zeros((Bp, self.cache_config.max_blocks_per_seq),
                              np.int32)
            start = np.zeros((Bp,), np.int32)
            last = np.zeros((Bp,), np.int32)
            for i, ch in enumerate(chunks):
                tokens[i] = ch.tokens
                tables[i] = self.scheduler.table_row(ch.request)
                start[i] = ch.start_pos
                last[i] = max(ch.n_valid - 1, 0)
        with tel.span("inference/prefill", args={"chunks": len(chunks)}):
            with tel.span("inference/prefill/dispatch"), \
                    self._collecting_moe():
                sampled, self.pool, moe_aux = self._prefill(
                    self.params, self.pool, jnp.asarray(tokens),
                    jnp.asarray(tables), jnp.asarray(start),
                    jnp.asarray(last), temp, self._next_key(),
                    kb=self._prefill_bucket(chunks))
            with tel.span("inference/prefill/fetch"):
                sampled, moe_aux = jax.device_get((sampled, moe_aux))
        n_tokens = 0
        with tel.span("inference/commit"):
            if moe_aux is not None:
                self._ingest_moe_stats(moe_aux, tel, "prefill")
            for i, ch in enumerate(chunks):
                first = int(sampled[i]) if ch.is_last else None
                self.scheduler.chunk_done(ch, first, eos_token_id)
                n_tokens += ch.n_valid
        tel.inc_counter("inference/prefill_tokens", v=n_tokens,
                        help="prompt tokens written through prefill")
        return n_tokens

    def _step_decode(self, tel: Any, chunks, decode, temp,
                     eos_token_id) -> int:
        with tel.span("inference/pack", args={"kind": "decode"}):
            # exactly TWO decode program shapes ever compile (1 and
            # decode_burst): over-running a request's budget inside a
            # burst is safe (max_pos clamps writes, the host discards
            # surplus tokens), so the tail reuses the full-length program
            burst = 1 if (chunks or self.scheduler.prefilling) \
                else self.decode_burst
            B = self.max_slots
            tokens = np.zeros((B,), np.int32)
            kv_lens = np.zeros((B,), np.int32)
            max_pos = np.zeros((B,), np.int32)
            tables = np.zeros((B, self.cache_config.max_blocks_per_seq),
                              np.int32)
            for req in decode:
                s = req.slot
                tokens[s] = req.generated[-1]
                kv_lens[s] = req.prefilled + len(req.generated) - 1
                max_pos[s] = len(req.prompt) + req.max_new_tokens - 1
                tables[s] = self.scheduler.table_row(req)
        with tel.span("inference/decode_burst",
                      args={"burst": burst, "batch": len(decode)}):
            with tel.span("inference/decode_burst/dispatch"):
                with self._collecting_moe():
                    toks, self.pool, moe_aux = self._decode(burst)(
                        self.params, self.pool, jnp.asarray(tokens),
                        jnp.asarray(kv_lens), jnp.asarray(tables),
                        jnp.asarray(max_pos), temp, self._next_key())
            with tel.span("inference/decode_burst/fetch"):
                toks, moe_aux = jax.device_get((toks, moe_aux))  # [burst, B]
        with tel.span("inference/commit"):
            if moe_aux is not None:
                self._ingest_moe_stats(moe_aux, tel, "decode", steps=burst)
            accepted = self.scheduler.decode_burst_done(decode, toks,
                                                        eos_token_id)
        tel.inc_counter("inference/decode_tokens", v=accepted,
                        help="decode tokens accepted by the scheduler")
        return accepted

    def generate(self, prompts: List[List[int]], max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 eos_token_id: Optional[int] = None,
                 ) -> List[List[int]]:
        """Drive the scheduler to completion over a ragged prompt batch.
        Returns the generated-token lists in prompt order."""
        self._key = jax.random.PRNGKey(seed)
        reqs = [self.put(p, max_new_tokens) for p in prompts]
        t0 = time.perf_counter()
        total = 0
        while self.scheduler.has_work:
            total += self.step(temperature, eos_token_id)
        dt = time.perf_counter() - t0
        self.last_throughput = total / dt if dt > 0 else 0.0
        from ...telemetry import get_telemetry

        get_telemetry().set_gauge(
            "inference/tokens_per_sec", self.last_throughput,
            help="tokens/sec of the last generate() drive")
        return [r.generated for r in reqs]


def build_engine_v2(model: Any, params: Any = None,
                    cache_config: Optional[KVCacheConfig] = None,
                    max_batch_slots: int = 8,
                    prefill_chunk: int = 128,
                    prefill_batch: int = 2,
                    decode_burst: int = 8,
                    mesh: Any = None,
                    scheduler_factory: Optional[Callable] = None,
                    ledger_key: str = "inference_v2/kv_pool"
                    ) -> RaggedInferenceEngineV2:
    if params is None:
        params = model.init_params(jax.random.PRNGKey(0))
    return RaggedInferenceEngineV2(model, params, cache_config,
                                   max_batch_slots, prefill_chunk,
                                   prefill_batch, decode_burst,
                                   mesh=mesh, scheduler_factory=scheduler_factory,
                                   ledger_key=ledger_key)
