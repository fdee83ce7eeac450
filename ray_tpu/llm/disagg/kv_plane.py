"""KV-page plane: paged-KV slices as first-class shm objects.

The disaggregation data path. A prefill worker's paged pool holds the
prompt's KV in page-granular rows (``[L, page, PS, KV, hd]`` per pool);
:func:`ship_pages` slices the produced pages out of the pool and seals
each one DIRECTLY into the local shm arena via ``put_value(
prefer_shm=True)`` — the sharded plane's seal path — returning a
:class:`KVPageManifest`: token ids, per-page object refs, producing
node, nbytes. The manifest is pure metadata (~100 bytes/page); the page
bytes move shm -> shm (same node, zero-copy) or through the object
plane's pull protocol (cross node), never through a driver RPC frame.

A decode worker :func:`adopt_pages` the manifest — one batched get over
the page refs, stacked into scatter-ready arrays — and the engine's
``submit_prefilled`` writes them into free pages of its OWN pool. Pages
are int8-KV aware: a quantized pool ships its ``q``/``s`` components as
separate refs so both stay zero-copy numpy reads on the adopting side.

Page granularity is what makes the pages SHAREABLE: a cached prefix of
``k`` full pages is exactly the first ``k`` entries of any manifest over
the same token prefix, so the prefix cache (prefix_cache.py) pins page
entries, and a suffix prefill reuses the cached entries without
resealing a byte (vLLM's PagedAttention sharing argument, applied
cross-request AND cross-worker).

Fault story: every ship/adopt passes the ``llm.kv_ship`` chaos point
(ctx ``phase``="seal"/"adopt") — ``error``/``drop`` surface as
:class:`KVShipError` (the scheduler re-prefills), ``kill`` dies mid-
adoption (the decode-death window the checked-in
``tests/plans/llm_decode_kill.json`` plan exercises).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field

import numpy as np

from ray_tpu.core import tiering
from ray_tpu.core.ref import ObjectRef
from ray_tpu.devtools import chaos
from ray_tpu.llm.disagg import telemetry

log = logging.getLogger(__name__)

# shipped-but-not-yet-adopted pages are this process's coldest referenced
# bytes; the tracker offers them to the raylet's cooperative spill
_staging: tiering.ColdTracker | None = None


def _staging_tracker() -> tiering.ColdTracker:
    global _staging
    if _staging is None:
        _staging = tiering.ColdTracker("kv_staging")
    return _staging


def untrack_staging(entry: "KVPageEntry") -> None:
    """Remove a page entry's components from this process's staging
    tracker (the prefix cache takes ownership at insert)."""
    if _staging is None:
        return
    for ref in entry.refs.values():
        _staging.untrack(ref.id.binary())


class KVShipError(Exception):
    """KV pages failed to ship/adopt (sealed copy lost, injected fault).
    Always recoverable by re-prefilling the prompt."""

    #: ship typed through the actor plane (core/worker.py _as_task_error)
    #: — the disagg scheduler classifies on this type to pick the
    #: re-prefill leg instead of the re-adopt leg
    _rt_error_passthrough = True


def _core():
    from ray_tpu.core import api

    return api.get_core()


@dataclass
class KVPageEntry:
    """One KV page: component refs (``k``/``v``, or ``k.q``/``k.s``/
    ``v.q``/``v.s`` for int8 pools), the node whose arena sealed them,
    and the payload byte count.

    The ``(tier, spill_path, spill_offset)`` leg is ADVISORY tiering
    metadata (core/tiering.py): tier-1 means the sealing node moved the
    bytes to its spill directory — consumers never branch on it, the
    object plane restores transparently on the next get/pull; the cache
    and ledgers use it to tell a disk hit from a shm hit."""

    refs: dict[str, ObjectRef]
    node: bytes | None = None
    nbytes: int = 0
    tier: int = tiering.TIER_SHM
    spill_path: str = ""
    spill_offset: int = 0


@dataclass
class KVPageManifest:
    """Token ids + page refs for one prompt's KV (the ShardManifest
    shape at page granularity). ``token_ids`` covers exactly
    ``len(pages) * page_size`` positions rounded down to the prompt
    length; pickling ships the manifest and the embedded refs ride the
    borrower protocol, so every holder owns real borrows on the pages."""

    token_ids: tuple
    page_size: int
    kv_dtype: str  # "native" | "bf16" | "int8"
    pages: list[KVPageEntry] = field(default_factory=list)

    @property
    def n_tokens(self) -> int:
        return len(self.token_ids)

    @property
    def n_pages(self) -> int:
        return len(self.pages)

    @property
    def nbytes(self) -> int:
        return sum(p.nbytes for p in self.pages)

    def full_pages(self) -> int:
        """Pages completely covered by token_ids — the shareable span
        (the last page of a ragged prompt is partially written and only
        adoptable by a request whose prefix covers ALL its tokens)."""
        return self.n_tokens // self.page_size

    def prefix(self, n_pages: int) -> "KVPageManifest":
        """Sub-manifest over the first ``n_pages`` pages, SHARING the
        page entries (and therefore the refs) — the cache-insert view."""
        n_pages = min(n_pages, self.n_pages)
        return KVPageManifest(
            token_ids=tuple(self.token_ids[: n_pages * self.page_size]),
            page_size=self.page_size,
            kv_dtype=self.kv_dtype,
            pages=self.pages[:n_pages],
        )


def manifest_nbytes(m: KVPageManifest) -> int:
    """Deterministic wire-size estimate of the manifest (what actually
    crosses the driver/actor RPC plane for a disagg request): header +
    token ids + ~(oid + owner address + node id) per component ref."""
    n_refs = sum(len(p.refs) for p in m.pages)
    return 48 + 8 * len(m.token_ids) + 96 * n_refs


# ------------------------------------------------------------ pool slicing
def _pool_components(pool, page_ids) -> dict[str, np.ndarray]:
    """Host copies of the selected pages, one array per pool component:
    ``{"": [L, n, PS, KV, hd]}`` for plain pools, ``{"q": ..., "s": ...}``
    for int8. ONE device->host transfer per component."""
    import jax.numpy as jnp

    idx = jnp.asarray(np.asarray(page_ids, np.int32))
    if isinstance(pool, dict):
        return {"q": np.asarray(pool["q"][:, idx]),
                "s": np.asarray(pool["s"][:, idx])}
    return {"": np.asarray(pool[:, idx])}


# the adoption scatter lives beside the other pool-shape ops in
# llm/llama.py (scatter_pages); re-exported here for the adopting side
# (the engine's admission, the prefill workers)
from ray_tpu.llm.llama import scatter_pages  # noqa: E402,F401


def _chaos_kv_ship(phase: str, **ctx):
    """Fire the ``llm.kv_ship`` fault point; map injected faults onto
    the plane's real failure surface (KVShipError)."""
    try:
        act = chaos.point("llm.kv_ship", phase=phase, **ctx)
    except chaos.ChaosError as e:
        raise KVShipError(f"kv_ship {phase}: {e}") from e
    if act is not None and act.kind == "drop":
        # "the pages were lost in flight": the scheduler's recovery
        # window — re-prefill from the cached prefix or from scratch
        raise KVShipError(f"kv_ship {phase}: pages dropped (injected)")


def ship_pages(kpool, vpool, page_ids, token_ids, *, page_size: int,
               kv_dtype: str = "native",
               trace_ctx=None) -> KVPageManifest:
    """Seal the KV pages ``page_ids`` (pool row indices, prompt order)
    into the local shm arena and return their manifest.

    ``token_ids`` are the prompt tokens the pages cover. Runs where the
    pool lives (the prefill worker); the driver only ever sees the
    returned manifest. ``trace_ctx`` (an owning request's captured
    (trace_id, span_id)) tags the seal as a ``pull``-stage span in the
    request's trace when sampled — wave-coalesced callers capture it at
    enqueue, direct callers inherit the ambient context.
    """
    core = _core()
    node = core.node_id.binary() if core.node_id is not None else None
    t0 = time.perf_counter_ns()
    kc = _pool_components(kpool, page_ids)
    vc = _pool_components(vpool, page_ids)
    entries: list[KVPageEntry] = []
    shipped = 0
    for i in range(len(page_ids)):
        if chaos.ENABLED:
            _chaos_kv_ship("seal", page=i)
        refs: dict[str, ObjectRef] = {}
        nbytes = 0
        for side, comps in (("k", kc), ("v", vc)):
            for name, arr in comps.items():
                page = np.ascontiguousarray(arr[:, i])
                key = side if not name else f"{side}.{name}"
                refs[key] = core.put_value(page, prefer_shm=True)
                nbytes += int(page.nbytes)
        entry = KVPageEntry(refs=refs, node=node, nbytes=nbytes)
        entries.append(entry)
        shipped += nbytes
        if core.store is not None:
            tracker = _staging_tracker()
            per = max(1, nbytes // max(1, len(refs)))
            for ref in refs.values():
                tracker.track(ref.id.binary(), per, entry)
    m = KVPageManifest(token_ids=tuple(int(t) for t in token_ids),
                       page_size=int(page_size), kv_dtype=kv_dtype,
                       pages=entries)
    telemetry.record(telemetry.KV_SHIP, time.perf_counter_ns() - t0,
                     shipped, trace_ctx=trace_ctx)
    telemetry.count(pages_shipped=len(entries), kv_array_bytes=shipped,
                    kv_driver_bytes=manifest_nbytes(m))
    return m


def adopt_pages(manifest: KVPageManifest,
                extra: KVPageManifest | None = None, *,
                role: str = "decode"):
    """Fetch a manifest's pages (one batched get: zero-copy out of local
    shm when same-node, object-plane pull otherwise) and stack them into
    scatter-ready ``(k_stack, v_stack)`` component dicts/arrays.

    ``extra`` appends a second manifest's pages (a cached prefix plus
    the request's suffix adopt as ONE scatter). ``role`` is pure chaos
    context ("decode" for engine admission, "prefill" for a suffix
    wave's prefix adoption) so a fault plan can target one side of the
    plane. Raises :class:`KVShipError` on injected loss and
    ``ObjectLostError`` when a page's sealed bytes are gone and cannot
    be recovered.
    """
    from ray_tpu.core import api

    pages = list(manifest.pages) + (list(extra.pages) if extra else [])
    if not pages:
        raise ValueError("empty manifest")
    if chaos.ENABLED:
        _chaos_kv_ship("adopt", pages=len(pages), role=role)
    t0 = time.perf_counter_ns()
    keys = sorted(pages[0].refs)
    flat = [p.refs[k] for p in pages for k in keys]
    # cross-node adoption: prefetch the whole manifest's pages in ONE
    # batched pull_objects round trip through the local raylet, hinted
    # with each page's sealing node — the get below then reads every
    # component zero-copy out of local shm (same-node manifests skip
    # this entirely: everything is already local). Best effort; the get
    # path keeps its per-ref pull/recovery fallbacks.
    core = _core()
    if core.store is not None:
        hints: dict = {}
        sizes: dict = {}
        owners: dict = {}
        for p in pages:
            per = max(1, p.nbytes // max(1, len(p.refs)))
            for k in keys:
                oid = p.refs[k].id
                if not core.store.contains(oid):
                    hints.setdefault(oid, set()).add(p.node)
                    sizes[oid] = per
                    owners[oid.hex()] = (p, per)
        if len(hints) >= 2:
            t_pull = time.perf_counter_ns()
            try:
                res = core._run_sync(
                    core.pull_objects_batch(
                        hints, sizes=sizes,
                        timeout_s=core.cfg.pull_admission_timeout_s),
                    timeout=60)
            except Exception:
                # loop-resident caller, or a stalled pull hitting the
                # bridge timeout: strictly an optimization — the get
                # below keeps its own per-ref pull/recovery fallbacks
                res = {}
                log.debug("batched KV prefetch skipped", exc_info=True)
            bp = (res or {}).get("_bp")
            if bp:
                # the raylet's admission window shed part of this
                # adoption: surface typed back-pressure so the scheduler
                # retries elsewhere instead of OOMing this arena
                from ray_tpu.serve.exceptions import BackPressureError

                raise BackPressureError(
                    f"kv adoption shed by pull admission "
                    f"({len(bp)}/{len(hints)} pages queued past deadline)",
                    retry_after_s=float(max(bp.values())))
            restored = (res or {}).get("_restored") or ()
            if restored:
                disk_bytes = 0
                for h in restored:
                    ent = owners.get(h)
                    if ent is None:
                        continue
                    p, per = ent
                    disk_bytes += per
                    # promoted back to shm by the restore
                    p.tier = tiering.TIER_SHM
                    p.spill_path = ""
                telemetry.record(telemetry.RESTORE,
                                 time.perf_counter_ns() - t_pull, disk_bytes)
                telemetry.count(kv_disk_bytes=disk_bytes,
                                pages_restored=len(restored))
    vals = api.get(flat)
    nk = len(keys)
    by_page = [vals[i * nk:(i + 1) * nk] for i in range(len(pages))]
    fetched = sum(int(getattr(v, "nbytes", 0)) for v in vals)

    def stack(side: str):
        comp_names = [k for k in keys if k.split(".")[0] == side]
        out = {}
        for ck in comp_names:
            j = keys.index(ck)
            out["" if "." not in ck else ck.split(".", 1)[1]] = np.stack(
                [bp[j] for bp in by_page], axis=1)
        return out[""] if list(out) == [""] else out

    k_stack, v_stack = stack("k"), stack("v")
    dm = manifest_nbytes(manifest) + (manifest_nbytes(extra) if extra else 0)
    telemetry.record(telemetry.KV_SHIP, time.perf_counter_ns() - t0,
                     fetched)  # adopt runs in the request's context
    telemetry.count(pages_adopted=len(pages), adoptions=1,
                    kv_array_bytes=fetched, kv_driver_bytes=dm)
    return k_stack, v_stack
