"""RaggedInferenceEngineV2 — the FastGen-style serving engine.

Reference: ``deepspeed/inference/v2/engine_v2.py`` [K] —
``InferenceEngineV2.put(uids, tokens)`` over a ragged batch with blocked KV
cache and Dynamic SplitFuse scheduling (SURVEY §2.5 row "Inference v2").

TPU-first: instead of ragged kernels over dynamic shapes, the engine
compiles a small number of fixed-shape programs and reuses them for any
request mix (XLA traces once; raggedness lives in int32 metadata).  A round
is ONE program call:

* ``decode_burst`` of ``k`` steps — ``k`` successive decode steps for all
  ``max_batch_slots`` sequences in one device program: sampling happens
  in-graph (greedy or temperature) and only int32 token ids return to the
  host — no per-token logits round-trip: ``[k, B]``, a token a sequence a
  step, or, of a model that drafts (below), ``[k, B, 2]``, a step's one
  or two.
  Page tables are fully reserved at admission (prompt + generation budget),
  so a burst never needs host page allocation mid-flight.
* the ONE-step decode program **carrying the round's prefill chunks** —
  ``chunk`` prompt tokens for each of up to ``prefill_batch`` sequences ride
  in the step as rows in front of the decode rows, writing KV pages
  through each row's block table (Dynamic SplitFuse composes a forward
  pass from decode tokens plus prompt chunks: long prompts become several
  chunks, each beside a decode step).  Every layer's weights, every active
  expert and the head cross HBM once for all the rows; a separate prefill
  call beside a one-step decode call read them twice (PERF.md §6, PR 37).
  While nothing decodes yet the decode rows are dead, as idle slots are.

Architecture deltas (norms, positions, FFN, head) live in
``adapters.ModelAdapterV2`` — llama/mistral/mixtral AND OPT serve on the
same engine (reference keeps per-arch model implementations under
``inference/v2/model_implementations`` [K]).

Every program donates the pools (one for each kind of attention layer the
adapter states: ``adapters.AttentionKind``) and carries them WHOLE through
its layer scan, addressed by ``(layer, page)``: a step's rows (decode) and
pages (chunks) are scattered into a pool in place and attention reads it
where it lies.  No program forms one layer's slice of a pool (see
``_layer_step`` for why), so KV updates are in-place in HBM and no call
moves more of the cache than it reads or writes.  How a row lies in a pool
(planes, rings, a latent row) and every access to it is
``kv_cache.KVLayout``'s, one a kind (``self.layouts``); this module holds
the programs and the round.

A model whose sequences carry a recurrent STATE beside their keys (the
adapter's ``state_kinds``) has a pool a batch slot for it in the same dict
(``kv_cache.StateLayout``, ``self.state_layouts``), riding the same carry:
the middle of each layer's branch that is not row-wise (the adapter's
``mix_chunk`` for chunk rows, ``mix_decode`` for decode rows) is given a
group of rows with its sequences' state, and what it returns is written
back in place at ``(the kind's layer, slot)`` (``_layer_step``).  A request's slot is its
batch slot from admission on, so a chunk's state is found as a ring is.

The scan runs over the PERIODS of the adapter's layer pattern, a period's
layers unrolled in the step, after the pattern's leading layers; a model
whose layers are all alike has one kind, no leading layer and a period of
one.  A pattern's entry names the layer's PART: an attention kind (with
whatever the family has behind it in the layer), a state kind whose mixer
is a layer of its own, or the FFN alone; each part's pool has as many
layers as the pattern has of the part, and a layer is handed its place
among those.

**A second call in flight.**  ``step_ahead`` (the serving front-end's
entry) plans, packs and dispatches the round's call FIRST, behind the
previous round's call, which is still running, and only then fetches and
commits that one; it returns with the new call in flight.  The device goes
from one call to the next without waiting for the host: the read-back, the
commit, the plan, the packs, the dispatch and whatever the front-end does
between two rounds all run under it.  Two things make that possible.  The
scheduler plans from what has been DISPATCHED, beside what has been
committed (``Request.ahead_*``): everything a plan reads is settled once a
call is out, except an EOS and, of a model that drafts, how MANY tokens the
call yields (planned is the least, a token a step).  And a row whose
newest token is still on the device takes it there: every program returns
its rows' newest tokens as one small array, the next call's argument, and
a host-packed map says which rows read it (``_dispatch``); a drafting
engine's rows take their token, their draft AND their length there
(``_seq``), always, since the host cannot know a length it has not
fetched.  The invariant that keeps this safe:
**a call is committed by what it was packed under** (``_settle``): a row
whose request ended, was cancelled, preempted or moved between dispatch
and commit is passed over, as a burst's surplus tokens always were.  And
it relies on the device running calls **in the order of dispatch**: pages
a request gives back are handed out at once, while a call in flight may
still write them, because the call that writes the next owner's keys
comes later.  ``step`` and ``settle`` leave nothing in flight.

**A step of one token or two.**  Where the adapter states a layer that
drafts (``adapters.DraftLayer``: a multi-token-prediction layer behind the
trunk), the two programs are :meth:`_draft_burst_fn`'s: a decode row is TWO
rows, the sequence's newest token and the draft of the one after it, one
grid row of two tokens in the paged kernel; the trunk's sample of the first
is emitted, and the second's too where the first IS the draft (greedy; at
a temperature no draft is accepted and the stream is one-token
sampling's); the drafting layer then runs behind the trunk, on the decode
rows and on a chunk's rows alike, its keys one more layer of its kind's
pool, and leaves the next draft.  A rejected draft's key is overwritten by
the next step's first row; a ring is sized for the two rows
(``KVCacheConfig.with_rings``).  The scheduler commits what a call yielded
(``decode_burst_done``), a budget may end between a step's two tokens, and
a call is committed by the LENGTH it found (returned with its tokens).
Such a model's seat holds part of its sequence (``state_slots``): no shared
prefix, and a preempted request starts over.  The other adapters state no
such layer and their programs hold nothing of it
(``tests/unit/inference/test_v2_programs_unchanged.py``).

A call is numbered as it is dispatched (``_Call.call``), and with the
telemetry hub on its spans in the two rounds carry that number; the hub's
own accounting for a step runs last (``_observe``).  The programs take
their small arguments as NumPy arrays (one transfer inside the call, not
an upload each) and their sampling keys, one a call in the order of
dispatch, from a chain split 256 links at a time (``_next_key``).
"""

from __future__ import annotations

import contextlib
import functools
import time
from collections import deque
from typing import (Any, Callable, Deque, Dict, List, NamedTuple, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...ops.masks import local_attention_mask
from ...ops.pallas.paged_attention import (pages_per_step,
                                           paged_decode_attention,
                                           paged_decode_attention_tp,
                                           paged_decode_impl,
                                           query_tokens_per_row)
from ...parallel.mesh import AXIS_TENSOR, strip_manual_axes
from ...telemetry import get_telemetry, numerics, startup_span
from ...telemetry.memory import get_memory_ledger
from ...telemetry.perf import get_compile_tracker, tracked_jit
from ...utils.jax_compat import shard_map
from ...utils.logging import log_dist
from .adapters import (AttentionKind, ModelAdapterV2, StateRows,
                       make_adapter)
from .kv_cache import (KVCacheConfig, init_kv_pool, kv_layouts,
                       state_layouts)
from .scheduler import RaggedScheduler, Request, RequestState


class _Row(NamedTuple):
    """A decode row as its call was packed."""
    request: Request
    slot: int                   # the row, and the column of its tokens
    position: int               # where its first step writes its key


class _Call(NamedTuple):
    """One program call, from its dispatch to its commit a round later.
    It is committed by what it was PACKED under: a chunk at its start, a
    decode row at its slot and position, wherever the request is by then
    (:meth:`RaggedInferenceEngineV2._settle`)."""
    chunks: list                # the prefill chunks riding in it
    decode: List[_Row]          # the rows decoding in it
    steps: int                  # 1 (it may carry chunks) or the burst
    #: on the device: (tokens, firsts, gate stats, the rows' lengths as the
    #: call found them: a drafting engine's, else None)
    outputs: tuple
    eos_token_id: Optional[int]     # the EOS id to accept under
    call: int                   # its number, the engine's count of calls
    ahead: bool                 # dispatched with the call before uncommitted
    kb: Optional[int]           # the chunks' page bucket
    kv_lens: np.ndarray         # [B] as packed: the decode rows' lengths
    max_pos: np.ndarray         # [B] as packed: their last positions
    #: the dispatch span's start (``perf_counter``; None with the hub off)
    dispatched: Optional[float]


@jax.jit
def _split_chain(key: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """256 links of ``key, sub = split(key)``: (the last key, the subs)."""
    def link(key, _):
        key, sub = jax.random.split(key)
        return key, sub
    return jax.lax.scan(link, key, None, length=256)


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
                 ledger_key: str = "inference_v2/kv_pool"):
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
        #: beside it: the pages a compute step of each kind's paged kernel
        #: was built with (``pages_per_step``), by the kind's name (its
        #: decode rows' call) and ``<name>/chunk`` (a latent kind's chunk
        #: rows' call), and the tokens a grid row of that second call
        #: holds (``query_tokens_per_row``), by the kind's name
        self.last_attn_pages_per_step: Dict[str, int] = {}
        self.last_attn_query_tokens: Dict[str, int] = {}
        #: the rows in a tile of the dropless expert layer's grouped
        #: matmuls (``moe_grouped_matmul.tile_rows_for``), by program
        #: (``n_steps<k>``: 1 carries the chunks), set as each is traced
        self.last_moe_tile_rows: Dict[str, int] = {}
        self._tp = int(mesh.shape.get("tensor", 1)) if mesh is not None else 1
        self.kinds: Dict[str, AttentionKind] = {
            k.name: k for k in self.adapter.kinds}
        for kind in self.kinds.values():
            if self._tp > 1 and kind.kv_heads % self._tp:
                raise ValueError(
                    f"tensor axis {self._tp} must divide kv heads "
                    f"{kind.kv_heads} for TP serving")
        if prefill_chunk % self.cache_config.block_size:
            raise ValueError("prefill_chunk must be a multiple of block_size")
        if self.cache_config.max_seq_len % prefill_chunk:
            # keeps every chunk's page-table slice in range: dynamic_slice
            # clamps out-of-bounds starts, which would silently retarget a
            # chunk's KV writes onto the sequence's EARLIER pages
            raise ValueError("max_seq_len must be a multiple of prefill_chunk")
        if self._tp > 1 and self.adapter.state_kinds:
            raise NotImplementedError(
                f"tensor-parallel serving of a model with recurrent state "
                f"({[k.name for k in self.adapter.state_kinds]}): its "
                f"branch is not split over the tensor axis (heads over "
                f"chips, groups replicated: ROADMAP R7)")
        #: the adapter's layer that drafts (``adapters.DraftLayer``), or
        #: None: the engine decodes two rows a sequence a step because the
        #: model has such a layer, and for no other reason
        self.draft = self.adapter.draft
        if self.draft is not None and (self._tp > 1
                                       or self.adapter.state_kinds):
            raise NotImplementedError(
                "a drafting layer under tensor-parallel serving, or beside "
                "a recurrent state: neither is built (ROADMAP R8)")
        # a drafting sequence's newest token, draft and length lie in its
        # batch slot on the device (``_seq``), as a recurrent state does:
        # its pages alone are not the sequence
        self.cache_config = self.cache_config.with_rings(
            self.kinds.values(), max_batch_slots, prefill_chunk,
            row_tokens=1 if self.draft is None else 2
        ).with_state(self.adapter.state_kinds
                     or (() if self.draft is None else (self.draft,)),
                     max_batch_slots)
        #: every access to a kind's pool: no code here indexes a pool array
        self.layouts = kv_layouts(self.adapter, self.cache_config)
        #: the same for the recurrent state a model carries beside its
        #: keys (empty where it carries none)
        self.state_layouts = state_layouts(self.adapter, self.cache_config)
        #: the attention kinds beside which a state kind's branch runs in
        #: the same layer (``StateKind.beside``)
        self._state_beside = frozenset(
            k.beside for k in self.adapter.state_kinds if k.beside)
        #: layers run, by part, as the last program traced counted them
        self.last_layers_by_part: Dict[str, int] = {}
        #: the serving plane swaps in its prefix-sharing scheduler here —
        #: same planner surface, refcounted page reservations
        make_sched = scheduler_factory or RaggedScheduler
        self.scheduler = make_sched(self.cache_config, max_batch_slots,
                                    prefill_chunk, prefill_batch)
        # the weights are served as they were handed over (no adapter
        # stacks, casts or re-lays them); over a tensor axis they are put
        # in their shards
        with startup_span("startup/place/weights", {"tensor": self._tp}):
            if self._tp > 1:
                spec_tree = self.model.param_specs(params)
                self.params = jax.tree.map(
                    lambda p, s: jax.device_put(
                        p, NamedSharding(mesh, strip_manual_axes(*s))),
                    params, spec_tree)
        with startup_span("startup/place/pools", {"tensor": self._tp}):
            if self._tp > 1:
                # allocate the pool DIRECTLY into its sharding — a serving
                # config sizes the pool near HBM capacity, so transiently
                # materializing it replicated would OOM at startup
                ad, cc = self.adapter, self.cache_config
                self.pool = tracked_jit(
                    lambda: init_kv_pool(ad, cc), "inference_v2/pool_init",
                    tracker=get_compile_tracker(),
                    out_shardings=NamedSharding(
                        mesh, P(None, None, None, AXIS_TENSOR, None)))()
            else:
                self.pool = init_kv_pool(self.adapter, self.cache_config)
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
        self._decode_jits: Dict[int, Callable] = {}
        with startup_span("startup/engine_v2/programs"):
            # the two programs of a round, as jitted callables: the step
            # that carries chunks and the burst.  Nothing is compiled here
            for n_steps in {1, self.decode_burst}:
                self._decode(n_steps)
        self._reseed(0)
        #: the calls dispatched and not committed yet, oldest first: one
        #: between two rounds, a second behind it inside ``step_ahead``
        self._inflight: Deque[_Call] = deque()
        #: the last call's ``newest`` (zeros before the first): the next
        #: call's argument whether it reads it or not, so that every call
        #: of a program has the one signature
        self._newest = np.zeros((max_batch_slots + self.prefill_batch,),
                                np.int32)
        #: a drafting engine's sequences AS THE LAST CALL DISPATCHED LEAVES
        #: THEM, by batch slot: the position of the newest token, that
        #: token and the draft of the one after it.  The next call's
        #: argument and result: the host does not know how far a call in
        #: flight gets (one token a step or two), so it packs no length; a
        #: sequence's last prefill chunk seats it here
        self._seq = None if self.draft is None else {
            name: np.zeros((max_batch_slots,), np.int32)
            for name in ("len", "tok", "draft")}
        #: program calls dispatched so far: the next call's number
        self._calls = 0
        #: MoE serving telemetry (ISSUE 19): when the model routes through
        #: a MOELayer, the decode program additionally returns the gate's
        #: per-expert load so the router/autoscaler can see hot experts.
        #: One persistent moe-only collector is active at trace time; the
        #: stats ride the program's output pytree ([L, E] load fractions
        #: averaged over the burst), so cached calls pay one tiny extra
        #: device→host transfer and zero recompiles.
        self._moe_coll = (
            numerics.Collector(probes=False, moe=True, tag="serving")
            if getattr(model, "_moe_layer", None) is not None else None)
        #: host-side rolling per-expert load (fractions, sum≈1) and the
        #: derived max/mean imbalance — the router's placement signal
        self.last_moe_stats: Optional[Dict[str, Any]] = None
        #: (entry name, width) of each column block of the stats a decode
        #: program packs (the model's, whatever the program's rows and
        #: steps), written as one is traced
        self._moe_columns: List[Tuple[str, int]] = []
        # the pools' and the router's gauges are worked out when the
        # registry is read, not in every round; the hub holds the hook
        # weakly
        get_telemetry().add_collect_hook(self._publish_gauges)
        log_dist(f"inference v2: pool={self.cache_config.num_blocks}"
                 f"x{self.cache_config.block_size} tokens, "
                 f"slots={max_batch_slots}, chunk={prefill_chunk}"
                 f"x{prefill_batch}, burst={self.decode_burst}, "
                 f"adapter={type(self.adapter).__name__}")

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------

    def _layer_step(self, params, lp, l, part, at, pools, x_flat,
                    positions_flat, write_fn, attend_fn, mix_fn=None):
        """One layer: the PART the pattern names for it (``part``, the
        ``at``-th layer of that part, which is its place in the part's
        pool), through the adapter's hooks for the part.
        ``write_fn``/``attend_fn``/``mix_fn`` are the rows' own:
        :meth:`_decode_rows`, :meth:`_chunk_rows`, or both
        (:meth:`_beside`).

        * an attention kind: qkv → KV write → attention → ``post_attn``
          (the output projection and whatever the family has behind it in
          the same layer: most an FFN, some nothing).  Where a state kind
          rides BESIDE it (``StateKind.beside``), the state's branch runs
          on the same input and joins the residual before ``post_attn``;
          its layer in the state pool is ``at`` too.
        * a state kind: ``mix_in`` → the groups' state moved → ``mix_out``,
          added to the residual.  ``mix_in`` and ``mix_out`` are row-wise;
          the middle is the rows' own (``mix_fn``), on layer ``at`` of the
          state pool.
        * ``adapters.FFN``: the adapter's ``ffn_layer``, given ``at``.

        ``pools`` holds, for each kind, the WHOLE pool, a carry of the
        layer scan: the write scatters rows or pages at ``(at, page)`` and
        attention reads layer ``at``'s pages where they lie.  No layer's
        slice of a pool is ever formed: a slice of a scanned stack handed
        to a custom call (the paged kernel) is copied out and the updated
        layer copied back (``kv_cache``'s module docstring).  Write, then
        attend: only the written pool lives on, so the write stays in
        place."""
        ad = self.adapter

        def mixed(x_flat, pools):
            # row-wise in and out, over all the rows at once; the state is
            # moved group by group in between
            p = ad.mix_in(lp, x_flat)
            y, pools = mix_fn(lp, p, pools, at)
            return x_flat + ad.mix_out(lp, p, y), pools

        if part in self.kinds:
            kind = self.kinds[part]
            q, kk, vv = ad.qkv(lp, x_flat, positions_flat, kind)
            pools = dict(pools, **{part: write_fn(
                pools[part], kind, at, kk, vv)})
            attn = attend_fn(q, pools[part], kind, at, ad.sink(lp))
            if part in self._state_beside:
                x_flat, pools = mixed(x_flat, pools)
            x_flat = ad.post_attn(lp, x_flat, attn, params, l)
        elif part in self.state_layouts:
            x_flat, pools = mixed(x_flat, pools)
        else:
            x_flat = ad.ffn_layer(lp, x_flat, params, at)
        return x_flat, pools

    def _per_kv_shard(self, fn, in_specs, out_specs):
        """``fn`` over pool arrays (``"pool"``), rows of heads
        (``"heads"``) and replicated arguments (``"all"``), as it runs:
        as it is on one chip; under tensor-parallel serving on each chip's
        KV heads and the query heads of their groups (heads are
        independent), through a ``shard_map`` over the whole mesh.  So the
        chunk rows address the LOCAL pool as the paged kernel does, through
        its page matrices (a bitcast of the carried buffer:
        ``KVLayout.write_pages`` / ``gather_pages``).  Under GSPMD that
        view would merge the sharded head dim with the tokens, and the
        ``(block, page)`` view it forced made XLA:TPU re-lay the carried
        pool out tokens-minor around every access (a pool of fewer KV
        heads than a vreg has sublanes: 2.5 GB each way of every call at
        the hybrid cell's size before PR 31, and inside every layer of the
        step that carries chunks, where the kernel's custom call pins the
        other layout)."""
        if self._tp == 1:
            return fn
        spec = {"pool": P(None, None, None, AXIS_TENSOR, None),
                "heads": P(None, AXIS_TENSOR, None), "all": P()}
        return shard_map(
            fn, mesh=self.mesh, in_specs=jax.tree.map(spec.get, in_specs),
            out_specs=jax.tree.map(spec.get, out_specs), check_vma=False,
            axis_names=set(self.mesh.axis_names))

    def _scan_layers(self, params, pools, x, positions_flat, write_fn,
                     attend_fn, mix_fn=None):
        """The layers of every program: the pattern's leading layers, then
        a scan over its periods; each layer is handed its place among the
        layers of its own part (:meth:`_layer_step`).  Carry: the
        activations and the pools;
        ``xs``: what the adapter's ``layers(params)`` holds, a period's
        slice a step, and the period's index; ``ys``: the MoE gate's
        stats (``moe_stats`` inside the FFN), which must leave the scan
        as ``ys`` — names ride the dict keys (a leading layer's are not
        collected: they would lack the scan's axis).  A leaf in ``xs`` is
        sliced whatever is done with it later, so what an adapter wants
        whole it leaves out of ``layers()`` and reads from ``params``,
        which its hooks are given (as the pools are read at ``lk``
        here)."""
        ad = self.adapter
        pattern = ad.pattern
        # a layer's place among the layers of its own part (its layer in
        # the part's pool): the leading layers of the part first, then
        # period by period
        parts = set(pattern.leading) | set(pattern.period)
        first = {name: pattern.leading.count(name) for name in parts}
        a_period = {name: pattern.period.count(name) for name in parts}
        self.last_layers_by_part = {
            name: first[name] + pattern.periods * a_period[name]
            for name in parts}
        with numerics.suppressed():
            seen = dict.fromkeys(parts, 0)
            for name, lp in zip(pattern.leading, ad.leading_layers(params)):
                x, pools = self._layer_step(
                    params, lp, None, name, seen[name], pools, x,
                    positions_flat, write_fn, attend_fn, mix_fn)
                seen[name] += 1

        def period(carry, xs):
            x, pools = carry
            pp, p = xs
            mark = numerics.scan_mark()
            seen = dict.fromkeys(parts, 0)
            for j, (name, lp) in enumerate(zip(pattern.period,
                                               ad.period_layers(pp, p))):
                x, pools = self._layer_step(
                    params, lp, p * len(pattern.period) + j, name,
                    first[name] + p * a_period[name] + seen[name], pools, x,
                    positions_flat, write_fn, attend_fn, mix_fn)
                seen[name] += 1
            return (x, pools), numerics.scan_drain(mark)

        (x, pools), stats = jax.lax.scan(
            period, (x, pools),
            (ad.layers(params), jnp.arange(pattern.periods, dtype=jnp.int32)))
        numerics.scan_collect(stats)  # keep the per-period axis
        return x, pools

    def _chunk_rows(self, tokens, tables, start_pos, rings, kb, slots=None,
                    last_idx=None):
        """A round's prefill chunks as rows of the one-step program: up to
        ``Bp`` sequences' chunks, ``tokens [Bp, C]`` at positions
        ``start_pos[r] + [0..C)``; rows beyond the live chunk count carry
        all-zero tables (page 0 = scratch).  ``kb`` (static): the page
        bucket they attend over (:meth:`_prefill_bucket`); ``rings [Bp]``:
        each row's ring's first page, where a kind recycles (else None).
        Which pages a kind's rows write and gather, or that they gather
        none and are rows of the paged kernel, ``T`` tokens a row
        (:meth:`_paged_attend`), is its layout's.  ``slots [Bp]``: each
        row's sequence's state slot where the model carries a state (0: a
        row that is no sequence's; else None), of whose ``C`` tokens
        ``last_idx + 1`` are real; a chunk at position 0 starts from
        zeros.  Returns (positions ``[Bp·C]``, the rows' part ``(Bp·C,
        write_fn, attend_fn, mix_fn)``: what :meth:`_layer_step` needs for
        them, :meth:`_beside`)."""
        ad = self.adapter
        Bp, C = tokens.shape
        positions = start_pos[:, None] + jnp.arange(C)[None, :]  # [Bp, C]
        page_cursor = start_pos // self.cache_config.block_size  # aligned

        def mask_over(kind, kpos):
            """``[Bp, 1(head), C, keys]`` over the gathered keys at
            ``kpos``: every row's the same ``[keys]``, or a row's own
            ``[Bp, keys]``, negative before its sequence's start."""
            if kpos is None:
                return None
            own = kpos.ndim == 2

            def one(p, k):
                mask = local_attention_mask(p, k, causal=True,
                                            window=kind.window)
                return mask & (k >= 0)[None] if own else mask

            return jax.vmap(one, in_axes=(0, 0 if own else None))(
                positions, kpos)[:, None]

        written, attended, masks = {}, {}, {}
        for name, layout in self.layouts.items():
            written[name], attended[name], kpos = layout.chunk_pages(
                tables, page_cursor, rings, C, int(kb))
            masks[name] = mask_over(self.kinds[name], kpos)

        def write_fn(pool, kind, l, kk, vv):
            # whole pages, scattered at (l, page) into the carried pool
            return self.layouts[kind.name].write_pages(
                pool, l, written[kind.name], kk, vv, self._per_kv_shard)

        def attend_fn(q, pool, kind, l, sink):
            layout = self.layouts[kind.name]
            if layout.chunks_through_kernel:
                # rows of the paged kernel through their sequence's table,
                # T consecutive tokens a row (the row's length is its last
                # token's): they share each fetched page and the row's
                # last, half-empty step (PERF.md §6, PR 46)
                T = query_tokens_per_row(
                    C, *layout.kernel_shapes(tables.shape[1], self._tp))
                out = self._paged_attend(
                    q.reshape((Bp * C // T, T) + q.shape[1:]), pool, kind, l,
                    sink, jnp.repeat(tables, C // T, axis=0),
                    positions.reshape(-1, T)[:, -1] + 1)
                return out.reshape((Bp * C,) + out.shape[2:])

            # gather only the attended pages and attend chunk-queries over
            # them — O(allocated), not O(max_seq_len)
            def attended_over(q, l, pages, mask, pool):
                kf, vf = layout.gather_pages(pool, l, pages)
                heads = q.shape[1]
                n_rep = heads // kf.shape[2]
                if n_rep > 1:
                    kf = jnp.repeat(kf, n_rep, axis=2)
                    vf = jnp.repeat(vf, n_rep, axis=2)
                qb = q.reshape(Bp, C, heads, kind.k_dim)
                scale = kind.scale or 1.0 / np.sqrt(kind.k_dim)
                s = jnp.einsum("bqhd,bkhd->bhqk", qb, kf
                               ).astype(jnp.float32) * scale
                s = jnp.where(mask, s, -1e30)
                if sink is None:
                    p = jax.nn.softmax(s, axis=-1).astype(ad.dtype)
                else:
                    # the sink: one more column, which carries no value
                    beside = jnp.broadcast_to(
                        sink.astype(jnp.float32)[None, :, None, None],
                        s.shape[:3] + (1,))
                    p = jax.nn.softmax(
                        jnp.concatenate([s, beside], axis=-1),
                        axis=-1)[..., :-1].astype(ad.dtype)
                attn = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
                return attn.reshape(Bp * C, heads, kind.v_dim)

            if sink is not None and self._tp > 1:
                # a head's sink would have to be split with the heads
                raise NotImplementedError(
                    "a sink under tensor-parallel serving")
            return self._per_kv_shard(
                attended_over, ("heads", "all", "all", "all",
                                jax.tree.map(lambda _: "pool", pool)),
                "heads")(q, jnp.asarray(l, jnp.int32), attended[kind.name],
                         masks[kind.name], pool)

        mix_fn = None
        if slots is not None:
            rows = StateRows(C, jnp.where(slots > 0, last_idx + 1, 0))
            (name, layout), = self.state_layouts.items()    # one kind

            def mix_fn(lp, p, pools, l):
                y, state = ad.mix_chunk(lp, p, layout.read_slots(
                    pools[name], l, slots, start_pos == 0), rows)
                return y, dict(pools, **{name: layout.write_slots(
                    pools[name], l, slots, state)})

        return positions.reshape(-1), (Bp * C, write_fn, attend_fn, mix_fn)

    def _paged_attend(self, q, pool, kind, l, sink, tables, lengths,
                      rows="chunk"):
        """Queries ``q [R, h, k_dim]``, a token a row, over layer ``l`` of
        a kind's pool through the paged kernel: row ``r`` attends over its
        first ``lengths[r]`` keys through ``tables[r]`` (the decode rows of
        every kind); or ``q [R, T, h, k_dim]``, ``T`` consecutive tokens a
        row and ``lengths[r]`` the last one's (``rows``: the ``"chunk"``
        rows of a latent kind, or a drafting engine's ``"decode"`` rows,
        the newest token and its draft).  The operands are the layout's;
        the route and its witnesses are decided here."""
        layout = self.layouts[kind.name]
        k, v, layer_tables, options, widths = layout.kernel_operands(
            pool, l, tables)
        shapes = layout.kernel_shapes(tables.shape[1], self._tp)
        kv_heads, heads = shapes[1:3]       # of a TP shard
        # what paged_decode_attention will run for these shapes
        # on this platform, by its own test
        impl = paged_decode_impl(heads, kv_heads, None, *widths)
        if impl == "reference" and jax.default_backend() == "tpu":
            get_telemetry().inc_counter(
                "inference/attn/reference_fallbacks",
                help="layers traced on a TPU whose paged decode "
                     "attention runs the jax.numpy reference: "
                     "the kernel refused their shapes")
        if impl != "reference":
            name, tokens = kind.name, 1
            if q.ndim == 4:     # several tokens a row: their own step
                tokens = q.shape[1]
                if rows == "chunk":
                    name = f"{name}/chunk"
                    self.last_attn_query_tokens[kind.name] = tokens
                else:
                    self.last_attn_query_tokens[f"{name}/{rows}"] = tokens
            self.last_attn_pages_per_step[name] = pages_per_step(
                *shapes, tokens)
        if self._tp > 1:
            # the kernel runs PER TP SHARD (heads are independent)
            if sink is not None:
                raise NotImplementedError(
                    "a sink under tensor-parallel serving")
            self.last_attn_path = f"{impl}_tp_shard_map"
            return paged_decode_attention_tp(
                q, k, v, layer_tables, lengths, mesh=self.mesh,
                window=kind.window)
        self.last_attn_path = impl
        return paged_decode_attention(q, k, v, layer_tables, lengths,
                                      sink=sink, **options)

    def _decode_rows(self, tables_of, wp, slots=None):
        """The decode rows of a step: row ``r`` writes its K and V at
        position ``wp[r]`` through its tables (``tables_of``: a kind's
        name → ``[B, max_blocks]``) and attends over the ``wp[r] + 1`` keys
        so far through the paged kernel.  ``slots [B]``: where the model
        carries a state, row ``r``'s state slot (``r + 1``) if the row is
        a sequence's and 0 if it is dead (it then moves no state); else
        None.  Returns the rows' part ``(B, write_fn, attend_fn,
        mix_fn)``: what :meth:`_layer_step` needs for them
        (:meth:`_beside`)."""
        bs = self.cache_config.block_size
        offsets = wp % bs
        page_ids = {name: table[jnp.arange(wp.shape[0]), wp // bs]
                    for name, table in tables_of.items()}

        def write_fn(pool, kind, l, kk, vv):
            # one scatter of [B, kv_h, d] rows at (l, page, offset)
            return self.layouts[kind.name].write_rows(
                pool, l, page_ids[kind.name], offsets, kk, vv)

        def attend_fn(q, pool, kind, l, sink):
            return self._paged_attend(q, pool, kind, l, sink,
                                      tables_of[kind.name], wp + 1)

        mix_fn = None
        if slots is not None:
            # the rows are the batch slots in order: their state is one
            # stretch of the layer, read and written where it lies
            rows = StateRows(1, (slots > 0).astype(jnp.int32))
            (name, layout), = self.state_layouts.items()    # one kind

            def mix_fn(lp, p, pools, l):
                state, held = layout.decode_operands(pools[name], l)
                y, state, held = self.adapter.mix_decode(lp, p, state, held,
                                                         rows)
                return y, dict(pools, **{name: layout.decode_written(
                    pools[name], l, state, held)})

        return wp.shape[0], write_fn, attend_fn, mix_fn

    def _draft_rows(self, tables_of, wp):
        """:meth:`_decode_rows` for a drafting step: ``wp [B, T]``, row
        ``r``'s ``T`` consecutive tokens (the newest and its draft) write
        at ``wp[r]`` and attend as ONE row of the paged kernel, ``T``
        tokens a grid row (``q [B, T, h, d]``: token ``t`` sees ``wp[r, -1]
        + 1 − (T − 1 − t)`` keys, under a window its own).  The program's
        rows are ``[B·T]``, a sequence's tokens side by side.  Where
        ``max_pos`` clamped both onto one position (a row past its
        budget) the scatter keeps either: nobody reads it."""
        B, T = wp.shape
        bs = self.cache_config.block_size
        flat = wp.reshape(-1)
        page_ids = {name: table[jnp.repeat(jnp.arange(B), T), flat // bs]
                    for name, table in tables_of.items()}

        def write_fn(pool, kind, l, kk, vv):
            return self.layouts[kind.name].write_rows(
                pool, l, page_ids[kind.name], flat % bs, kk, vv)

        def attend_fn(q, pool, kind, l, sink):
            out = self._paged_attend(
                q.reshape((B, T) + q.shape[1:]), pool, kind, l, sink,
                tables_of[kind.name], wp[:, -1] + 1, rows="decode")
            return out.reshape((B * T,) + out.shape[2:])

        return B * T, write_fn, attend_fn, None

    @staticmethod
    def _beside(parts):
        """``parts``: ``(rows, write_fn, attend_fn, mix_fn)`` of each group
        of a program's rows, in the rows' order → the three over all of
        them: a layer's K and V written group by group into the one
        carried pool, each group's queries attended its own way, the
        results concatenated; and, where the model carries a state (else
        the ``mix_fn``'s are None), each group's branch run on its own
        rows and state, group by group.  The groups read nothing of each
        other's writes (different requests, different pages), so their
        order is free; a slot's state is moved by the one group its
        request is in, and a dead decode row on a prefilling request's
        slot leaves it as the chunk before it wrote it."""
        if len(parts) == 1:
            return parts[0][1:]
        ends = np.cumsum([part[0] for part in parts]).tolist()
        spans = list(zip([0] + ends[:-1], ends))

        def write_fn(pool, kind, l, kk, vv):
            for (lo, hi), (_, write, _, _) in zip(spans, parts):
                pool = write(pool, kind, l, kk[lo:hi],
                             None if vv is None else vv[lo:hi])
            return pool

        def attend_fn(q, pool, kind, l, sink):
            return jnp.concatenate(
                [attend(q[lo:hi], pool, kind, l, sink)
                 for (lo, hi), (_, _, attend, _) in zip(spans, parts)])

        def mix_fn(lp, p, pools, l):
            outs = []
            for (lo, hi), (_, _, _, mix) in zip(spans, parts):
                # ``mix_in``'s rows: an array, or a tree of arrays a row
                out, pools = mix(lp, jax.tree.map(lambda a: a[lo:hi], p),
                                 pools, l)
                outs.append(out)
            return jnp.concatenate(outs), pools

        return (write_fn, attend_fn,
                mix_fn if parts[0][3] is not None else None)

    @staticmethod
    def _last_of_chunks(x, c_last, C):
        """``x [Bp·C + rows, …]``, the chunks' rows in front: each chunk's
        row ``c_last`` (its last valid one), then the other rows."""
        Bp = c_last.shape[0]
        last = jnp.take_along_axis(
            x[:Bp * C].reshape(Bp, C, -1), c_last[:, None, None],
            axis=1)[:, 0]
        return jnp.concatenate([last, x[Bp * C:]])

    def _decode_burst_fn(self, params, pool, tokens, fed, kv_lens, tables,
                         max_pos, temperature, key, rings=None, chunks=None,
                         slots=None, *, n_steps: int,
                         kb: Optional[int] = None):
        """``n_steps`` decode iterations entirely on device: each step
        writes KV at ``kv_lens`` through ``tables``, attends via the paged
        kernel, samples the next token in-graph and feeds it back.  Write
        positions clamp at ``max_pos`` (a slot that hit EOS/budget inside
        the burst only scribbles its own reserved pages; the host discards
        its surplus tokens).  ``rings [B]``: each row's ring's first page,
        where a kind recycles (else None).  ``slots``: where the model
        carries a recurrent state (else None), ``(the rows' [B], the
        chunks' [Bp] or None)``: each row's state slot, 0 for a row that
        is no sequence's (:meth:`_decode_rows`, :meth:`_chunk_rows`).

        ``fed``: ``(source [B], newest [B + Bp])``.  A row's first input
        token is ``tokens[r]`` where ``source[r] < 0``, else the previous
        call's ``newest[source[r]]`` (this program's last result, below):
        a token the host has not fetched yet (:meth:`_dispatch`).

        ``chunks`` (the one-step program only): the round's prefill chunks
        ``(tokens [Bp, C], tables, start_pos, last_idx, rings)``, whose
        ``Bp·C`` rows ride in the step IN FRONT of the decode rows
        (:meth:`_chunk_rows` under the page bucket ``kb``); their last
        valid rows are sampled beside the decode rows.

        Returns (token ids ``[n_steps, B]``, pools, the gate's stats
        packed or None, the chunks' sampled tokens ``[Bp]`` or None, the
        rows' newest tokens ``[B + Bp]``: the last step's, then the chunks'
        or zeros)."""
        ad = self.adapter
        B = tokens.shape[0]
        source, newest = fed
        tokens = jnp.where(source < 0, tokens,
                           newest[jnp.maximum(source, 0)])
        tables_of = {name: layout.row_tables(tables, rings)
                     for name, layout in self.layouts.items()}
        if chunks is not None:
            if n_steps != 1:
                raise ValueError("chunks ride in the one-step program")
            c_tokens, c_tables, c_start, c_last, c_rings = chunks
            Bp, C = c_tokens.shape
            c_pos, riding = self._chunk_rows(
                c_tokens, c_tables, c_start, c_rings, kb,
                None if slots is None else slots[1], c_last)

        def one_step(carry, key):
            tokens, kv_lens, pool = carry
            step_mark = numerics.scan_mark()
            wp = jnp.minimum(kv_lens, max_pos)  # [B] write positions
            ids, pos = tokens, wp
            parts = [self._decode_rows(
                tables_of, wp, None if slots is None else slots[0])]
            if chunks is not None:
                ids = jnp.concatenate([c_tokens.reshape(-1), tokens])
                pos = jnp.concatenate([c_pos, wp])
                parts.insert(0, riding)
            x = ad.embed(params, ids, pos)
            x, pool = self._scan_layers(params, pool, x, pos,
                                        *self._beside(parts))
            if chunks is not None:
                # of a chunk's rows only the last valid one is sampled
                x = self._last_of_chunks(x, c_last, C)
            x = ad.finalize(params, x)
            sampled = _sample(ad.logits(params, x), temperature, key)
            nxt = sampled[-B:]
            firsts = sampled[:-B] if chunks is not None else None
            step_stats = numerics.scan_drain(step_mark)
            return (nxt, kv_lens + 1, pool), (nxt, firsts, step_stats)

        keys = jax.random.split(key, n_steps)
        (_, _, pool), (toks, firsts, stats) = jax.lax.scan(
            one_step, (tokens, kv_lens, pool), keys)
        numerics.scan_collect(stats, combine=True)  # mean over the burst
        firsts = None if firsts is None else firsts[0]
        newest = jnp.concatenate([
            toks[-1], jnp.zeros((self.prefill_batch,), jnp.int32)
            if firsts is None else firsts])
        return toks, pool, self._pack_moe_stats(n_steps), firsts, newest

    def _draft_burst_fn(self, params, pool, seq, live, tables, max_pos,
                        temperature, key, rings=None, chunks=None, *,
                        n_steps: int, kb: Optional[int] = None):
        """:meth:`_decode_burst_fn` of a model with a drafting layer
        (``adapters.DraftLayer``): ``n_steps`` steps of TWO rows a
        sequence, each yielding one token or two.

        ``seq``: ``{"len", "tok", "draft"} [B]``, the sequences as the call
        before left them, on the device (``self._seq``): the position of
        each one's newest token, the token, and the draft of the one after
        it.  ``live [B]``: the slots whose request decodes in this call;
        the others' rows are dead (position 0 of page 0) and their ``seq``
        stays.  A step runs the trunk on rows ``(tok, len)`` and ``(draft,
        len + 1)``, both clamped at ``max_pos``; the first row's sample
        ``a`` is emitted; if it IS the draft (and the call is greedy: at a
        temperature above 0 no draft is accepted, so the stream is
        one-token sampling's, token for token in distribution), the second
        row's sample ``a'`` is emitted too.  The drafting layer then runs
        on both rows (input: the row's ``u`` and the token that follows
        it, ``a`` and ``a'``), writes its keys at the rows' positions in
        its layer of its kind's pool, and the last emitted row's gives the
        next draft.  A rejected draft leaves a key at ``len + 1`` in every
        layer, the drafting layer's too: the next step's first row is
        written there before anything reads it.

        ``chunks``: as :meth:`_decode_burst_fn`'s, and behind them
        ``(follow [Bp], seat [Bp])``: the drafting layer runs over a
        chunk's rows too, row ``i`` reading prompt token ``i + 1``; the
        chunk's last valid row reads ``follow`` (the next chunk's first
        token) or, where ``follow < 0`` (the prompt's last chunk), the
        first token the call samples, and its draft seats the sequence in
        ``seq[seat]`` (``seat = B``: no sequence's).  So the trunk's ``u``
        never leaves the call.

        Returns (ids ``[n_steps, B, 2]``, −1 where a step emitted no
        second token; ``seq["len"]`` as it came in; pools; the gate's
        stats; the chunks' first tokens ``[Bp]`` or None; ``seq`` going
        out)."""
        ad, draft = self.adapter, self.draft
        B = live.shape[0]
        tables_of = {name: layout.row_tables(tables, rings)
                     for name, layout in self.layouts.items()}
        if chunks is not None:
            if n_steps != 1:
                raise ValueError("chunks ride in the one-step program")
            (c_tokens, c_tables, c_start, c_last, c_rings,
             c_follow, c_seat) = chunks
            Bp, C = c_tokens.shape
            c_pos, riding = self._chunk_rows(c_tokens, c_tables, c_start,
                                             c_rings, kb)

        def sampled_rows(x):
            """The rows that go through the head: of a chunk's only its
            last valid one, then every decode row."""
            return x if chunks is None else self._last_of_chunks(x, c_last, C)

        def one_step(carry, key):
            seq, pool = carry
            step_mark = numerics.scan_mark()
            wp = jnp.minimum(
                jnp.where(live, seq["len"], 0)[:, None] + jnp.arange(2),
                max_pos[:, None])                   # [B, 2]
            ids = jnp.stack([seq["tok"], seq["draft"]], axis=1).reshape(-1)
            pos = wp.reshape(-1)
            parts = [self._draft_rows(tables_of, wp)]
            if chunks is not None:
                ids = jnp.concatenate([c_tokens.reshape(-1), ids])
                pos = jnp.concatenate([c_pos, pos])
                parts.insert(0, riding)
            write_fn, attend_fn, _ = self._beside(parts)
            x = ad.embed(params, ids, pos)
            x, pool = self._scan_layers(params, pool, x, pos, write_fn,
                                        attend_fn)
            u = ad.finalize(params, x)
            sampled = _sample(ad.logits(params, sampled_rows(u)),
                              temperature, key)
            own = sampled[-2 * B:].reshape(B, 2)
            firsts = sampled[:-2 * B] if chunks is not None else None
            accept = live & (own[:, 0] == seq["draft"]) & (temperature <= 0)
            # the drafting layer on every row: the token that follows it
            follows = own.reshape(-1)
            if chunks is not None:
                after = jnp.where(c_follow < 0, firsts, c_follow)
                shifted = jnp.concatenate(
                    [c_tokens[:, 1:], jnp.zeros((Bp, 1), jnp.int32)], axis=1)
                follows = jnp.concatenate([jnp.where(
                    jnp.arange(C)[None, :] == c_last[:, None],
                    after[:, None], shifted).reshape(-1), follows])
            mark = numerics.scan_mark()
            with jax.named_scope(draft.name):
                y, pool = self._layer_step(
                    params, ad.draft_layer(params), None, draft.kind,
                    draft.at, pool, ad.draft_in(params, u, follows), pos,
                    write_fn, attend_fn)
                drafts = jnp.argmax(
                    ad.draft_logits(params, sampled_rows(y)),
                    axis=-1).astype(jnp.int32)
            # its gate's stats: one more sparse layer behind the scan's
            for name, value in (numerics.scan_drain(mark) or {}).items():
                numerics.active().add(name.partition(":")[2], value[None])
            d_own = drafts[-2 * B:].reshape(B, 2)
            pick = lambda pair: jnp.where(accept, pair[:, 1], pair[:, 0])
            seq = {"len": seq["len"] + jnp.where(live, 1 + accept, 0),
                   "tok": jnp.where(live, pick(own), seq["tok"]),
                   "draft": jnp.where(live, pick(d_own), seq["draft"])}
            emitted = jnp.stack(
                [own[:, 0], jnp.where(accept, own[:, 1], -1)], axis=1)
            seats = (firsts, drafts[:-2 * B]) if chunks is not None else None
            return (seq, pool), (emitted, seats, numerics.scan_drain(
                step_mark))

        lens_in = seq["len"]
        (seq, pool), (toks, seats, stats) = jax.lax.scan(
            one_step, (seq, pool), jax.random.split(key, n_steps))
        numerics.scan_collect(stats, combine=True)  # mean over the burst
        self.last_layers_by_part[draft.name] = 1
        firsts = None
        if seats is not None:
            # a prompt's last chunk seats its sequence: the prompt's length,
            # the first token and its draft
            firsts, first_drafts = seats[0][0], seats[1][0]
            hit = c_seat[:, None] == jnp.arange(B)[None, :]     # [Bp, B]
            seated = {"len": c_start + c_last + 1, "tok": firsts,
                      "draft": first_drafts}
            seq = {name: jnp.where(
                hit.any(axis=0),
                jnp.sum(jnp.where(hit, seated[name][:, None], 0), axis=0),
                seq[name]) for name in seq}
        return (toks, lens_in, pool, self._pack_moe_stats(n_steps), firsts,
                seq)

    def _decode(self, n_steps: int) -> Callable:
        fn = self._decode_jits.get(n_steps)
        if fn is None:
            body = (self._decode_burst_fn if self.draft is None
                    else self._draft_burst_fn)
            fn = tracked_jit(functools.partial(body, n_steps=n_steps),
                             "inference_v2/decode_burst",
                             tracker=get_compile_tracker(),
                             static_context={"n_steps": n_steps},
                             donate_argnums=(1,), static_argnames=("kb",))
            self._decode_jits[n_steps] = fn
        return fn

    # ------------------------------------------------------------------
    # serving surface
    # ------------------------------------------------------------------

    def put(self, prompt: List[int], max_new_tokens: int = 32) -> Request:
        """Admit one request (reference ``engine.put`` role)."""
        return self.scheduler.add_request(prompt, max_new_tokens)

    # -- MoE serving telemetry -----------------------------------------

    def _pack_moe_stats(self, n_steps: int) -> Optional[jnp.ndarray]:
        """Inside a program of ``n_steps`` steps, after its layer scan:
        the active collector's entries (each with the axis the scan gave
        it: ``[periods]`` or ``[periods, E]``, one entry for each sparse
        layer of a period) as ONE float32 array ``[sparse layers,
        columns]``, so that the host fetches the router's stats in one
        transfer beside the tokens (fetched entry by entry they cost the
        serving round 7.5 ms of host, PERF.md PR 27).  Which columns hold
        what is a fact of the trace, kept in ``_moe_columns``; so is the
        tile the expert layer was just traced with
        (``last_moe_tile_rows``)."""
        tile = getattr(getattr(self.model, "_moe_layer", None),
                       "last_tile_rows", None)
        if tile is not None:
            self.last_moe_tile_rows[f"n_steps{n_steps}"] = tile
        coll = numerics.active()
        named = coll.harvest() if coll is not None else None
        if not named:
            return None
        # an entry a sparse layer of the period, in program order, each
        # with the periods' axis in front: [periods, …] → rows (period,
        # layer of the period), so a row is a sparse layer in model order
        by_name: Dict[str, List[Any]] = {}
        for key in sorted(named):
            by_name.setdefault(key.partition(":")[2], []).append(
                named[key].astype(jnp.float32))
        blocks = []
        for name, entries in sorted(by_name.items()):
            periods = entries[0].shape[0]
            # behind the scan's: a drafting layer's, one row of its own
            # (where the scan is one period long it stacks like theirs)
            behind = [e.reshape(e.shape[0], -1) for e in entries
                      if e.shape[0] != periods]
            entries = [e for e in entries if e.shape[0] == periods]
            block = entries[0] if len(entries) == 1 else jnp.stack(
                [e.reshape(periods, -1) for e in entries], axis=1)
            block = block.reshape(periods * len(entries), -1)
            blocks.append((name, jnp.concatenate([block] + behind)
                           if behind else block))
        self._moe_columns = [(name, int(b.shape[1])) for name, b in blocks]
        return jnp.concatenate([b for _, b in blocks], axis=1)

    def _ingest_moe_stats(self, packed: np.ndarray
                          ) -> Optional[Dict[str, np.ndarray]]:
        """Host side of one call's gate stats: what the router and the
        autoscaler read (``last_moe_stats``: per-expert load, imbalance,
        drop rate; the gauges of the same names are worked out from it
        when the registry is read, :meth:`_publish_gauges`).  Returns the
        stats by entry name for :meth:`_count_moe`.  Telemetry must never
        kill a serving round: a layout that does not fit the array, or an
        entry the gate did not report, is skipped."""
        layout = self._moe_columns
        if packed.ndim != 2 or packed.shape[1] != sum(w for _, w in layout):
            return None
        cols, at = {}, 0
        for name, width in layout:
            cols[name] = packed[:, at:at + width]
            at += width
        load = cols.get("moe/load")
        if load is not None:
            load = load.astype(np.float64)                    # [L, E]
            mean = load.mean(axis=1)
            # max/mean of the hottest layer: 1.0 = a balanced router
            hottest = np.where(mean > 0, load.max(axis=1)
                               / np.maximum(mean, 1e-12), 0.0).max()
            drop = cols.get("moe/drop_rate")
            self.last_moe_stats = {
                "load": load.mean(axis=0).tolist(),
                "imbalance": float(hottest),
                "drop_rate": float(drop.mean()) if drop is not None else 0.0}
        return cols

    @staticmethod
    def _count_moe(tel: Any, cols: Dict[str, np.ndarray], steps: int
                   ) -> None:
        """The dropless layer's counters for one call (``steps`` steps,
        the stats their mean; a step that carries chunks counts their
        rows too)."""
        if "moe/experts_active" not in cols or "moe/assignments" not in cols:
            return
        # rows x k is the same in every layer; non-empty groups are
        # not, nor is what lands on a share of the experts
        computed = float(cols["moe/assignments"].mean()) * steps
        tel.inc_counter(
            "inference/moe/assignments", v=computed,
            help="token-to-expert assignments computed HERE, a layer "
                 "(the mean over layers) a step of a call: rows x k "
                 "where every expert is held, the held experts' part "
                 "of it under expert parallelism")
        routed = cols.get("moe/assignments_routed")
        tel.inc_counter(
            "inference/moe/assignments_routed",
            v=computed if routed is None else float(routed.mean()) * steps,
            help="token-to-expert assignments the router made: rows "
                 "x k, a step of a call, wherever the experts live")
        tel.inc_counter(
            "inference/moe/experts_active",
            v=float(cols["moe/experts_active"].sum()) * steps,
            help="held experts with at least one row (whose weights "
                 "the grouped matmul reads), summed over the layers "
                 "that have experts and steps")
        if "moe/rows_computed" in cols:
            tel.inc_counter(
                "inference/moe/rows_computed",
                v=float(cols["moe/rows_computed"].mean()) * steps,
                help="rows of the tiles in use that the grouped matmuls "
                     "multiply, padding and all, a layer (the mean over "
                     "layers) a step of a call: inference/moe/assignments "
                     "over it is how full the tiles are")

    def _publish_gauges(self) -> None:
        """The registry's collect hook: the gauges of the pools and of
        the router's last stats, from state the engine keeps anyway.  It
        runs on the reader's thread, beside a round, and takes no lock:
        the free list's length, the slots and ``last_moe_stats`` (a dict
        replaced whole) are safe to read there."""
        tel = get_telemetry()
        if not tel.enabled:
            return
        for name, layout in self.layouts.items():
            tel.set_gauge(
                f"inference/kv/pages_in_use/{name}",
                float(layout.pages_in_use(self.scheduler)),
                help="pages of the kind's pool that live sequences hold "
                     "(a recycled kind: at most a ring a sequence), each "
                     "over all the kind's layers")
        for name, pages in self.last_attn_pages_per_step.items():
            tel.set_gauge(
                f"inference/attn/pages_per_step/{name}", float(pages),
                help="pages a compute step of the kind's paged decode "
                     "kernel fetches and scores, as the traced programs "
                     "were built (<kind>/chunk: its chunk rows' call)")
        for name, tokens in self.last_attn_query_tokens.items():
            tel.set_gauge(
                f"inference/attn/query_tokens_per_row/{name}", float(tokens),
                help="consecutive tokens of a prefill chunk that share a "
                     "grid row of the kind's paged kernel, as the traced "
                     "programs were built")
        for name, rows in self.last_moe_tile_rows.items():
            tel.set_gauge(
                f"inference/moe/tile_rows/{name}", float(rows),
                help="rows in a tile of the expert layer's grouped "
                     "matmuls, as the program (n_steps<k>: 1 carries the "
                     "chunks) was built: twice the router's mean group")
        for name, layers in self.last_layers_by_part.items():
            tel.set_gauge(
                f"inference/layers/{name}", float(layers),
                help="layers of the model that are this part (an attention "
                     "kind, a state kind or the FFN alone), as the traced "
                     "programs ran them")
        for layout in self.state_layouts.values():
            tel.set_gauge(
                "inference/ssm/slots_in_use",
                float(sum(r is not None for r in self.scheduler.slots)),
                help="batch slots whose recurrent state a live sequence "
                     "holds")
            tel.set_gauge(
                "inference/ssm/state_bytes", float(layout.pool_bytes),
                help="bytes of the recurrent state's pool: every layer's "
                     "every slot's, and the scratch slot's")
        stats = self.last_moe_stats
        if not stats:
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
        return (numerics.collecting(self._moe_coll)
                if self._moe_coll is not None else contextlib.nullcontext())

    def moe_load_imbalance(self) -> float:
        """Router-facing hot-expert signal: max/mean expert load of the
        last decode burst (1.0 = balanced; 0.0 = no MoE data yet)."""
        if not self.last_moe_stats:
            return 0.0
        return float(self.last_moe_stats.get("imbalance", 0.0))

    def _next_key(self, tel: Any) -> np.ndarray:
        """The next call's sampling key: ``key, sub = split(key)`` as ever,
        256 links of the chain in one program and one fetch (an eager
        split a call is two dispatches a round on the host's critical
        path).  The refill waits for the device, behind the call in
        flight: it has a span of its own."""
        if not self._subkeys:
            with tel.span("inference/keys"):
                self._key, subs = _split_chain(self._key)
                self._subkeys = list(np.asarray(subs)[::-1])
        return self._subkeys.pop()

    def _reseed(self, seed: int) -> None:
        self._key, self._subkeys = jax.random.PRNGKey(seed), []

    def _prefill_bucket(self, chunks) -> int:
        """Static page-bucket for a round's chunks: smallest power-of-two
        multiple of the chunk's page count that covers the deepest row's
        ``start_pos + chunk`` keys.  Bounded program count (log2 buckets),
        O(allocated) gather cost."""
        bs = self.cache_config.block_size
        mb = self.cache_config.max_blocks_per_seq
        if not any(lay.gathers_bucket for lay in self.layouts.values()):
            return mb       # no kind gathers a bucket: one program
        need = max((ch.start_pos + self.chunk) // bs for ch in chunks)
        kb = max(self.chunk // bs, 1)
        while kb < need:
            kb *= 2
        return min(kb, mb)

    def step(self, temperature: float = 0.0,
             eos_token_id: Optional[int] = None) -> int:
        """One scheduler step, complete when it returns: ONE program call
        (module docstring), fetched and committed (:meth:`step_ahead` +
        :meth:`settle`: nothing is in flight before or after).  Returns
        the number of tokens processed."""
        return self.step_ahead(temperature, eos_token_id) + self.settle()

    def step_ahead(self, temperature: float = 0.0,
                   eos_token_id: Optional[int] = None) -> int:
        """:meth:`step` for a caller that comes back: the round's call is
        planned, packed and dispatched FIRST, behind the previous round's
        call, and only then is that one fetched and committed (module
        docstring, "A second call in flight").  At most two calls are
        uncommitted at any time, one when this returns.  Returns the
        tokens committed in THIS call: the previous call's chunks and
        decode tokens.  A round with chunks runs the one-step decode
        program with the chunks' rows riding in it
        (:meth:`_decode_burst_fn`), the decode rows dead where nothing
        decodes yet; a round without runs the burst.  Where nothing can be
        planned (every budget ends in the call in flight), that call is
        committed and the next round plans again."""
        tel = get_telemetry()
        with tel.span("inference/step") as sp:
            with tel.span("inference/plan"):
                chunks, decode = self.scheduler.plan_step()
                sp.set(chunks=len(chunks), decoding=len(decode))
            sent = None
            if chunks or decode:
                sent = self._dispatch(tel, chunks, decode,
                                      np.float32(temperature), eos_token_id)
            n_tokens, done = self._settle(tel, keep=0 if sent is None else 1)
            if tel.enabled:
                self._observe(tel, sent, done)
        return n_tokens

    def settle(self) -> int:
        """Fetch and commit everything in flight, oldest first; returns
        the tokens it yielded.  What a call computed for a request that is
        no longer where the call left it is passed over (:meth:`_settle`):
        the request prefills that chunk, or decodes that position, again
        if it resumes.  After it ``req.generated``, ``req.prefilled`` and
        the pool are what a loop of :meth:`step` would have left."""
        tel = get_telemetry()
        n_tokens, done = self._settle(tel, keep=0)
        if tel.enabled:
            self._observe(tel, None, done)
        return n_tokens

    def _settle(self, tel: Any, keep: int) -> Tuple[int, List[tuple]]:
        """Fetch and commit the calls in flight, oldest first, all but the
        ``keep`` newest → (the tokens they yielded, what
        :meth:`_count_call` counts of each: empty with the hub off).  The
        spans of the wait and the commit carry the number the call was
        dispatched under.  **A call is committed by what it was packed
        under** (:class:`_Call`): a chunk only if its request is still
        prefilling at the chunk's start, a decode row only if its request
        is still running in the row's slot at the row's position.  So a
        request re-seated in a freed slot never receives its predecessor's
        token, and one that was cancelled, preempted, moved or ended (an
        EOS inside the call before) between dispatch and commit keeps
        nothing of the call: the row is counted as overrun."""
        n_tokens, done = 0, []
        while len(self._inflight) > keep:
            c = self._inflight.popleft()
            ident = {"call": c.call}
            with tel.span("inference/decode_burst",
                          args={"burst": c.steps, "batch": len(c.decode),
                                "call": c.call}) as burst:
                with tel.span("inference/decode_burst/fetch",
                              args=ident) as fetch:
                    # [burst, B] (a drafting engine: [burst, B, 2], -1
                    # where a step gave no second token), [Bp] or None, the
                    # gate's stats or None, the lengths the call found
                    toks, firsts, moe_aux, lens = jax.device_get(c.outputs)
                if tel.enabled:
                    # ``burst`` is the call's STEPS; what its rows yielded
                    slots = [row.slot for row in c.decode]
                    burst.set(tokens=int((toks[:, slots] >= 0).sum()))
            with tel.span("inference/commit", args=ident) as commit:
                moe = (None if moe_aux is None
                       else self._ingest_moe_stats(moe_aux))
                live = self._commit_chunks(c.chunks, firsts, c.eos_token_id)
                written = sum(ch.n_valid for ch in live)
                # length - 1: the position of a request's newest token,
                # which a drafting call reports as it found it (``lens``)
                rows = [row.request for row in c.decode
                        if row.request.state is RequestState.RUNNING
                        and row.request.slot == row.slot
                        and row.request.length - 1 == (
                            row.position if lens is None
                            else lens[row.slot])]
                # (a request that ends leaves its slot: read them first)
                kept = None if lens is None else [r.slot for r in rows]
                accepted = self.scheduler.decode_burst_done(
                    rows, toks, c.eos_token_id)
                commit.set(tokens=accepted)
            n_tokens += written + accepted
            if fetch.end is not None and commit.end is not None:   # hub on
                drafted = None
                if lens is not None:
                    # of the rows committed: a draft a step, and the steps
                    # that emitted their second token
                    drafted = (c.steps * len(kept),
                               int((toks[:, kept, 1] >= 0).sum()))
                done.append((c, live, written, accepted,
                             len(c.decode) - len(rows), moe,
                             fetch.end - fetch.start, commit.end, drafted))
        return n_tokens, done

    def _commit_chunks(self, chunks, firsts, eos_token_id) -> list:
        """The chunks a fetched call wrote, handed to the scheduler (after
        the fetch: its prefix index sees written pages); returns them.  A
        chunk whose request is not where the call left it (cancelled or
        preempted between the rounds) is passed over."""
        live = [(i, ch) for i, ch in enumerate(chunks)
                if ch.request.state is RequestState.PREFILL
                and ch.request.prefilled == ch.start_pos]
        for i, ch in live:
            self.scheduler.chunk_done(
                ch, int(firsts[i]) if ch.is_last else None, eos_token_id)
        return [ch for _, ch in live]

    def _observe(self, tel: Any, sent: Optional[_Call],
                 done: List[tuple]) -> None:
        """The hub's own accounting for a step, all of it in one place:
        after the dispatch and the commit, so that the host's chain holds
        in a traced run what it holds in an untraced one.  ``sent``: the
        call this step dispatched; ``done``: the calls it committed
        (:meth:`_settle`)."""
        if sent is None and not done:
            return
        with tel.span("inference/observe"):
            if sent is not None:
                live = [row.slot for row in sent.decode]
                self._count_cache_traffic(
                    tel, sent.kv_lens[live], sent.max_pos[live], sent.steps,
                    [ch.start_pos for ch in sent.chunks])
                self._count_state_traffic(tel, sent)
            for counted in done:
                self._count_call(tel, *counted)

    def _count_call(self, tel: Any, c: _Call, live, written: int,
                    accepted: int, overrun: int, moe, wait_s: float,
                    committed: float, drafted: Optional[tuple]) -> None:
        """A committed call's counters, and its record: the ring span
        ``inference/call`` from the start of its dispatch (a round ago)
        to the end of its commit."""
        if c.chunks:
            tel.inc_counter("inference/prefill_tokens", v=written,
                            help="prompt tokens written through prefill")
            tel.inc_counter("inference/chunk_tokens_beside_decode",
                            v=written if accepted else 0,
                            help="prompt tokens written by a call that "
                                 "also yielded a decode token: over "
                                 "prefill_tokens, the share of prefill "
                                 "work that rode a decode step")
        tel.inc_counter("inference/decode_tokens", v=accepted,
                        help="decode tokens accepted by the scheduler")
        bs = self.cache_config.block_size
        self._count_recycled(tel, [ch.start_pos // bs for ch in live],
                             [-(-ch.n_valid // bs) for ch in live])
        if moe is not None:
            self._count_moe(tel, moe, c.steps)
        chunk_rows = self.prefill_batch * self.chunk if c.chunks else 0
        rows_a_slot = 1
        if drafted is not None:
            rows_a_slot = 2
            self._count_drafts(tel, *drafted,
                               c.steps * 2 * self.max_slots + chunk_rows)
        tel.inc_counter("inference/calls",
                        help="program calls committed (one a round)")
        tel.inc_counter("inference/calls_dispatched_ahead",
                        v=1.0 if c.ahead else 0.0,
                        help="committed calls that were dispatched while "
                             "the call before them was still uncommitted: "
                             "the device went from one to the next without "
                             "waiting for the host")
        tel.inc_counter("inference/rows_overrun", v=overrun,
                        help="decode rows a committed call computed for a "
                             "request that had finished, been cancelled or "
                             "moved by then: planned before the call in "
                             "front of it was committed, and passed over")
        tel.inc_counter("inference/calls_with_chunks",
                        v=1.0 if c.chunks else 0.0,
                        help="committed calls that carried prefill chunks")
        tel.inc_counter("inference/rows_computed",
                        v=c.steps * rows_a_slot * self.max_slots + chunk_rows,
                        help="rows the committed calls computed: every "
                             "decode slot a step (two rows where the model "
                             "drafts), and every chunk row of a call that "
                             "carried chunks, live or not")
        tel.inc_counter("inference/chunk_rows_computed", v=chunk_rows,
                        help="the chunk rows among rows_computed "
                             "(prefill_batch x prefill_chunk a call that "
                             "carried chunks)")
        tel.inc_counter("inference/rows_live", v=accepted + written,
                        help="rows of the committed calls whose result "
                             "was kept: decode tokens accepted and valid "
                             "prompt tokens committed")
        if c.dispatched is not None:
            tel.tracer.add("inference/call", c.dispatched, committed, {
                "call": c.call, "steps": c.steps,
                "decode_rows": len(c.decode), "chunk_tokens": written,
                "accepted": accepted, "kb": c.kb, "wait_s": wait_s})

    @staticmethod
    def _count_drafts(tel: Any, drafted: int, accepted: int,
                      rows: int) -> None:
        """A committed call's drafting: ``drafted`` drafts went through the
        trunk (a row a step, of the rows committed), ``accepted`` of them
        were the trunk's own next token; the drafting layer ran ``rows``
        rows."""
        tel.inc_counter("inference/mtp/drafted", v=drafted,
                        help="drafts verified by the trunk: one a "
                             "committed decode row a step")
        tel.inc_counter("inference/mtp/accepted", v=accepted,
                        help="drafts that were the trunk's own next token: "
                             "their step emitted two tokens (whether the "
                             "budget kept the second or not)")
        tel.inc_counter("inference/mtp/rows", v=rows,
                        help="rows the drafting layer ran in the committed "
                             "calls: two a decode slot a step and every "
                             "chunk row, live or not")
        tel.inc_counter("inference/mtp/keys_taken_back",
                        v=drafted - accepted,
                        help="rejected drafts: each left a key in every "
                             "layer's cache, the drafting layer's too, "
                             "which the next step's first row overwrote")

    def _count_recycled(self, tel: Any, first_page, pages) -> None:
        """``pages`` logical pages a sequence from ``first_page`` on (arrays
        over sequences) were begun by a call: those past a ring's length
        overwrote a page that fell out of the window."""
        recycled = self.cache_config.pages_recycled(first_page, pages)
        if recycled is None:
            return
        tel.inc_counter(
            "inference/kv/window_pages_recycled", v=recycled,
            help="pages of the window layers' rings overwritten with a "
                 "later page of the same sequence (logical pages: each is "
                 "one page in every window layer)")

    def _pack_chunks(self, tel: Any, chunks) -> Tuple[Tuple, int, Any]:
        """``chunks`` as the one-step program takes them
        (:meth:`_decode_burst_fn`), their page bucket, and their rows'
        state slots (None where the model carries no state)."""
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
            rings = self.cache_config.ring_bases(
                Bp, ((i, ch.request.ring) for i, ch in enumerate(chunks)))
            slots = self.cache_config.state_rows(
                Bp, ((i, ch.request.slot) for i, ch in enumerate(chunks)))
            kb = self._prefill_bucket(chunks)
        return (tokens, tables, start, last, rings), kb, slots

    def _follow_and_seat(self, chunks) -> Tuple[np.ndarray, np.ndarray]:
        """What a drafting program takes behind a round's chunks
        (:meth:`_draft_burst_fn`): the prompt token that FOLLOWS each
        chunk (−1: the chunk is its prompt's last, and the first token the
        call samples follows it) and the batch slot a prompt's last chunk
        seats its sequence in (``max_slots``, which no slot is, for the
        others and for rows that carry no chunk)."""
        follow = np.zeros((self.prefill_batch,), np.int32)
        seat = np.full((self.prefill_batch,), self.max_slots, np.int32)
        for i, ch in enumerate(chunks):
            if ch.is_last:
                follow[i], seat[i] = -1, ch.request.slot
            else:
                follow[i] = ch.request.prompt[ch.start_pos + ch.n_valid]
        return follow, seat

    def _count_cache_traffic(self, tel: Any, kv_lens, max_pos, burst,
                             chunk_starts=()) -> None:
        """What the paged kernel reads of each kind's cache in a call
        (``KVLayout.keys_read``; ``chunk_starts``: the live chunks' first
        positions), and the ring pages its decode steps begin, from what
        the call packed: a decode step reads a row's keys so far, the one
        it writes among them."""
        # [burst, rows]: the length a row attends over at each step
        lengths = np.minimum(kv_lens[None, :] + np.arange(burst)[:, None],
                             max_pos[None, :]) + 1
        for name, layout in self.layouts.items():
            tel.inc_counter(
                f"inference/attn/keys_read_{name}",
                v=layout.keys_read(lengths, self.chunk, chunk_starts),
                help="keys a layer of the kind attends over through the "
                     "paged kernel, summed over decoding rows and decode "
                     "steps and, of a latent kind, over the chunk rows (a "
                     "KV head's; times layers, KV heads and row bytes: "
                     "what the kernel must read)")
        bs = self.cache_config.block_size
        self._count_recycled(tel, -(-kv_lens // bs),
                             (lengths[-1] - 1) // bs + 1 - -(-kv_lens // bs))

    def _count_state_traffic(self, tel: Any, sent: _Call) -> None:
        """What a call moves of the recurrent state, from what it packed:
        every step reads and writes every batch slot's state in every
        layer OF THE KIND (``StateKind.layers``: the layers that have a
        mixer, not the model's; a dead row's as it was), and a call that
        carries chunks its chunk rows' slots once more."""
        for layout in self.state_layouts.values():
            moved = (sent.steps * self.max_slots
                     + (self.prefill_batch if sent.chunks else 0)
                     ) * layout.kind.layers * layout.bytes_per_slot
            tel.inc_counter(
                "inference/ssm/state_bytes_read", v=moved,
                help="bytes of recurrent state the calls dispatched read: "
                     "every batch slot's a layer of the kind a decode "
                     "step, and a chunk row's slot a layer of the kind")
            tel.inc_counter(
                "inference/ssm/state_bytes_written", v=moved,
                help="bytes of recurrent state the calls dispatched wrote "
                     "back in place (as many as they read)")
            tel.inc_counter(
                "inference/ssm/decode_rows", v=sent.steps * len(sent.decode),
                help="one-token state updates of live sequences: decode "
                     "rows x steps of the calls dispatched (a layer's; "
                     "times the kind's layers and a slot's bytes twice: "
                     "what the updates must move)")
            tel.inc_counter(
                "inference/ssm/chunk_tokens",
                v=sum(ch.n_valid for ch in sent.chunks),
                help="prompt tokens that went through the chunk scan in "
                     "the calls dispatched (a layer's)")
            tel.inc_counter(
                "inference/ssm/chunks_from_zero",
                v=sum(ch.start_pos == 0 for ch in sent.chunks),
                help="chunks that began their sequence: their state "
                     "started from zeros, whatever the slot held")

    def _dispatch(self, tel: Any, chunks, decode, temp,
                  eos_token_id) -> _Call:
        """Pack and dispatch the round's one call: ``decode``'s rows and,
        riding in their step, ``chunks``; returns it, in flight.  Its
        outputs stay on the device until :meth:`_settle`.

        The rows are packed from what is PLANNED for each request (the
        scheduler's cursors over what has been dispatched).  Where the
        call in front is not committed yet, a row's newest token is still
        on the device: the row names where in that call's ``newest`` it
        lies (``source``; :meth:`_decode_burst_fn`'s ``fed``); every other
        row carries its token from the host (``source`` -1)."""
        # exactly TWO decode step counts ever compile (1, which carries
        # the chunks under their page bucket, and decode_burst):
        # over-running a request's budget inside a burst is safe (max_pos
        # clamps writes, the host discards surplus tokens), so the tail
        # reuses the full-length program
        burst, riding, riding_slots, bucket = self.decode_burst, None, None, {}
        if chunks:
            burst = 1
            riding, bucket["kb"], riding_slots = self._pack_chunks(tel,
                                                                   chunks)
        with tel.span("inference/pack", args={"kind": "decode"}):
            B = self.max_slots
            tokens = np.zeros((B,), np.int32)
            source = np.full((B,), -1, np.int32)
            live = np.zeros((B,), bool)     # a drafting engine's rows
            kv_lens = np.zeros((B,), np.int32)
            max_pos = np.zeros((B,), np.int32)
            tables = np.zeros((B, self.cache_config.max_blocks_per_seq),
                              np.int32)
            ahead = bool(self._inflight)
            # a request with tokens planned ahead has not left its slot
            # since the call in flight went out: its newest token is that
            # call's at the same slot, or, where its last chunk rode in
            # it, the chunk's first token behind the B slots
            firsts_at = {ch.request.uid: B + i for i, ch in enumerate(
                self._inflight[-1].chunks) if ch.is_last} if ahead else {}
            rows = []
            for req in decode:
                s = req.slot
                if self.draft is not None:
                    # its token, draft and length are the device's
                    # (``_seq``); ``position`` below is a lower bound, for
                    # the cache's counters alone
                    live[s] = True
                elif req.ahead_tokens:
                    source[s] = firsts_at.get(req.uid, s)
                else:
                    tokens[s] = req.generated[-1]
                position = len(req.prompt) + req.planned_tokens - 1
                kv_lens[s] = position
                max_pos[s] = len(req.prompt) + req.max_new_tokens - 1
                tables[s] = self.scheduler.table_row(req)
                rows.append(_Row(req, s, position))
            rings = self.cache_config.ring_bases(
                B, ((r.slot, r.ring) for r in decode))
            slots = self.cache_config.state_rows(
                B, ((r.slot, r.slot) for r in decode))
            self.scheduler.dispatched(chunks, decode, burst)
            self._calls += 1
        with tel.span("inference/decode_burst/dispatch",
                      args={"call": self._calls}) as sp, \
                self._collecting_moe():
            lens = None
            if self.draft is not None:
                if riding is not None:
                    riding += self._follow_and_seat(chunks)
                toks, lens, self.pool, moe_aux, firsts, self._seq = \
                    self._decode(burst)(
                        self.params, self.pool, self._seq, live, tables,
                        max_pos, temp, self._next_key(tel), rings, riding,
                        **bucket)
            else:
                toks, self.pool, moe_aux, firsts, self._newest = \
                    self._decode(burst)(
                        self.params, self.pool, tokens,
                        (source, self._newest), kv_lens, tables, max_pos,
                        temp, self._next_key(tel), rings, riding,
                        None if slots is None else (slots, riding_slots),
                        **bucket)
        call = _Call(chunks, rows, burst, (toks, firsts, moe_aux, lens),
                     eos_token_id, self._calls, ahead, bucket.get("kb"),
                     kv_lens, max_pos, sp.start)
        self._inflight.append(call)
        return call

    def generate(self, prompts: List[List[int]], max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 eos_token_id: Optional[int] = None,
                 ) -> List[List[int]]:
        """Drive the scheduler to completion over a ragged prompt batch.
        Returns the generated-token lists in prompt order."""
        self._reseed(seed)
        reqs = [self.put(p, max_new_tokens) for p in prompts]
        t0 = time.perf_counter()
        total = 0
        while self.scheduler.has_work:
            total += self.step(temperature, eos_token_id)
        dt = time.perf_counter() - t0
        self.last_throughput = total / dt if dt > 0 else 0.0
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
