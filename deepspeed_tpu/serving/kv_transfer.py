"""Page-granular KV transfer — the disaggregated prefill/decode wire.

Disaggregation (ISSUE 14 tentpole c) splits a request across two
replica processes: a *prefill* replica computes the prompt's KV and the
first token, then the finished KV pages stream DIRECTLY to the *decode*
replica (peer-to-peer — the bytes never transit the front door or the
rendezvous store), which seats the request and decodes from the
received pages.  This module is the wire format and the two pool
boundaries:

* :func:`page_payload` — one pool page as transportable bytes.  Real
  engines ship the page's K and V planes across all layers, as the
  cache's layout hands them over (``kv_cache.page_arrays``); the
  host-only synthetic engine ships a
  deterministic token-derived payload so the transfer machinery
  (chunking, checksum gates, rejection) is exercised end-to-end with no
  device.
* :func:`push_pages` — the client side of the decode worker's
  ``kv_page_begin`` / ``kv_page_chunk`` / ``kv_page_commit`` ops
  (modeled on the tier-2 replica transport): each page is chunked
  base64 with its OWN sha256, verified at the receiver before anything
  touches the pool — a torn or tampered page is rejected
  (``serving/kv_transfer_rejects_total``), never decoded from.
* :func:`inject_pages` — write verified payloads into the adopting
  engine's pool at the reserved block ids.

Counters: ``serving/kv_transfer_pages_total`` / ``_bytes_total`` on the
sending side, ``_received_total`` / ``_rejects_total`` on the receiver,
``serving/kv_transfer_skipped_pages_total`` for pages the decode-side
prefix trie already held (the cluster-wide KV tier at work).
"""

from __future__ import annotations

import base64
import hashlib
from typing import Any, Dict, List, Optional

import numpy as np

DEFAULT_KV_CHUNK_BYTES = 64 * 1024


def _sha256(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def page_payload(engine: Any, prompt: List[int], blocks: List[int],
                 page_index: int) -> Dict[str, Any]:
    """Serialize one KV page of a prefilled request.

    Returns ``{"raw": bytes, "sha256": str, "dtype": str, "shape":
    [...], "synthetic": bool}`` — ``raw`` is K-plane bytes followed by
    V-plane bytes (equal length, concatenated; the receiver splits at
    the midpoint), or the K-plane bytes alone where the pool has no V (a
    latent cache, whose rows hold their value)."""
    bs = int(engine.cache_config.block_size)
    pool = getattr(engine, "pool", None)
    if pool is None:
        # synthetic engine: no device pool — a deterministic payload
        # derived from the page's tokens keeps the checksum gate real
        toks = prompt[page_index * bs:(page_index + 1) * bs]
        arr = np.zeros((bs,), np.int32)
        arr[:len(toks)] = toks
        raw = arr.tobytes()
        return {"raw": raw + raw, "sha256": _sha256(raw + raw),
                "dtype": "int32", "shape": [bs], "synthetic": True}
    from ..inference.v2.kv_cache import page_arrays

    planes = page_arrays(engine.layouts, pool, blocks[page_index])
    raw = b"".join(p.tobytes() for p in planes)
    return {"raw": raw, "sha256": _sha256(raw), "dtype": str(planes[0].dtype),
            "shape": list(planes[0].shape), "synthetic": False}


def inject_pages(engine: Any, blocks: List[int],
                 staged: Dict[int, Dict[str, Any]]) -> None:
    """Write verified page payloads into ``engine.pool`` at the
    reserved block ids (``staged`` maps page index -> payload dict with
    ``raw``/``dtype``/``shape``), all in one batched write (it runs under
    the adopting front-end's lock).  Synthetic payloads are content-free
    bookkeeping — nothing to write."""
    pool = getattr(engine, "pool", None)
    if pool is None or not staged:
        return
    from ..inference.v2.kv_cache import write_page_arrays

    ids: List[int] = []
    pages: List[List[np.ndarray]] = []
    for page_index, p in sorted(staged.items()):
        if p.get("synthetic"):
            continue
        # equal parts, as exported: K then V, or K alone
        planes = np.frombuffer(p["raw"], dtype=np.dtype(p["dtype"]))
        ids.append(blocks[page_index])
        pages.append(list(planes.reshape(
            (-1,) + tuple(int(s) for s in p["shape"]))))
    if ids:
        write_page_arrays(engine.layouts, pool, ids, pages)


def push_pages(rpc_fn, rid: str, payloads: Dict[int, Dict[str, Any]],
               chunk_bytes: int = DEFAULT_KV_CHUNK_BYTES,
               timeout: Optional[float] = None,
               trace_id: Optional[str] = None) -> Dict[str, int]:
    """Stream page payloads to a decode worker through ``rpc_fn`` (one
    ``rpc(requests) -> replies`` callable bound to the target
    endpoint).  Each page rides its own begin/chunk*/commit triplet so
    the receiver's sha256 gate is PER PAGE — one corrupt page names
    itself instead of poisoning the whole transfer.  ``trace_id``
    stamps each page's ``begin`` message so a packet capture or a
    receiver-side log attributes the transfer to its request (ISSUE
    15 context propagation).  Raises ``RuntimeError`` on refusal
    (checksum mismatch, unknown rid)."""
    step = max(1, int(chunk_bytes))
    reqs: List[Dict[str, Any]] = []
    total = 0
    for page_index, p in sorted(payloads.items()):
        b64 = base64.b64encode(p["raw"]).decode("ascii")
        chunks = [b64[i:i + step] for i in range(0, len(b64), step)] \
            or [""]
        begin = {"op": "kv_page_begin", "rid": rid, "page": page_index,
                 "n": len(chunks), "sha256": p["sha256"],
                 "nbytes": len(p["raw"]), "dtype": p["dtype"],
                 "shape": p["shape"],
                 "synthetic": bool(p.get("synthetic"))}
        if trace_id:
            begin["trace"] = str(trace_id)
        reqs.append(begin)
        reqs += [{"op": "kv_page_chunk", "rid": rid, "page": page_index,
                  "i": i, "v": ch} for i, ch in enumerate(chunks)]
        reqs.append({"op": "kv_page_commit", "rid": rid,
                     "page": page_index})
        total += len(p["raw"])
    replies = rpc_fn(reqs) if timeout is None else rpc_fn(reqs, timeout)
    for r in replies:
        if not r.get("ok"):
            raise RuntimeError(
                f"kv transfer for {rid} refused: {r.get('err')}")
    from ..telemetry import get_telemetry

    tel = get_telemetry()
    tel.inc_counter("serving/kv_transfer_pages_total", v=len(payloads),
                    help="KV pages streamed prefill -> decode")
    tel.inc_counter("serving/kv_transfer_bytes_total", v=total,
                    help="raw KV bytes streamed prefill -> decode")
    return {"pages": len(payloads), "bytes": total}


class PageStager:
    """Receiver-side assembly of one in-flight KV transfer: chunked
    base64 per page, committed only when the page's sha256 matches.
    All calls are made under the owning worker's lock."""

    def __init__(self) -> None:
        #: page index -> {"n", "sha256", "chunks", "dtype", "shape"}
        self._inflight: Dict[int, Dict[str, Any]] = {}
        #: page index -> verified payload ({"raw", "dtype", ...})
        self.ready: Dict[int, Dict[str, Any]] = {}

    def begin(self, page: int, meta: Dict[str, Any]) -> None:
        self._inflight[page] = {
            "n": int(meta["n"]), "sha256": str(meta["sha256"]),
            "dtype": str(meta.get("dtype", "int32")),
            "shape": list(meta.get("shape", [])),
            "synthetic": bool(meta.get("synthetic")),
            "chunks": {}}

    def chunk(self, page: int, i: int, v: str) -> None:
        ent = self._inflight.get(page)
        if ent is None:
            raise ValueError(f"kv chunk for page {page} with no begin")
        ent["chunks"][int(i)] = str(v)

    def commit(self, page: int) -> int:
        """Verify + stage the page; returns its raw byte count.
        Raises ``ValueError`` on a checksum mismatch (the caller maps
        it to a refused reply + reject counter) — a failed page stays
        un-staged and may be retried."""
        ent = self._inflight.pop(page, None)
        if ent is None:
            raise ValueError(f"kv commit for page {page} with no begin")
        b64 = "".join(ent["chunks"].get(i, "")
                      for i in range(ent["n"]))
        raw = base64.b64decode(b64)
        if _sha256(raw) != ent["sha256"]:
            raise ValueError(
                f"kv page {page} failed the transfer checksum gate "
                f"(sha256 {_sha256(raw)[:12]}… != expected "
                f"{ent['sha256'][:12]}…) — page rejected")
        self.ready[page] = {"raw": raw, "dtype": ent["dtype"],
                            "shape": ent["shape"],
                            "synthetic": ent["synthetic"]}
        return len(raw)
