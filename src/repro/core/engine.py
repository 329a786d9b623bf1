"""PrefillOnly engine — the real-compute serving loop (paper §3).

Workflow (Figure 2):
  profile run   -> JCT model fit + prefix-KV budget (kv_policy / measured)
  submit()      -> tokenize-equivalent: hash-chain the request, enqueue
  step()        -> Algorithm 1 pick (continuous JCT calibration) -> batch
                   formation (prepacking) -> hybrid prefill (cache-hit
                   suffix path when possible) -> suffix-KV discard into the
                   block cache -> constrained single-token output (the
                   paper's P(Yes)/P(No) scoring)

This engine runs REAL forwards: the reduced preset on the CPU in tests, and
published widths on a TPU chip through the same code (``launch/serve.py``,
``chip_smoke.py``). Each engine commits its parameters to one device, so one
process can drive one replica per chip. Shapes are bucketed so jit compiles
a bounded set of programs.

Prepacked prefill (arXiv:2404.09529 / BatchLLM arXiv:2412.03594)
----------------------------------------------------------------
Bucketing rounds every suffix up to the next shape in ``suffix_buckets``, so
a 65-token request pays the FLOPs of a 128-token forward — on the paper's
short discriminative workloads up to ~50% of prefill compute is padding.
Instead of widening the batch axis (which §6.1 rejects for latency), the
engine packs several requests end-to-end into ONE sequence and restricts
attention to same-segment pairs (segment ids drive both tile-level skipping
and element masking in the kernels; RoPE positions restart at each segment
boundary). Single-token output makes this safe: each packed request needs
only its own last-row logits.

Batch formation preserves Algorithm 1: the *anchor* request is still the
scheduler's pick. First-fit-decreasing backfill fills the remaining
``pack_token_budget`` (counted in COMPUTED tokens) with further requests,
largest first — short requests ride in the padding slack that bucketing
would have burned anyway. Each packed request's KV is sliced out of the
packed forward and inserted into the prefix cache under its own hash chain
(suffix discard still applies), and the JCT model observes (computed tokens,
wall time) so SRJF-calibrated scoring stays calibrated for packed steps.

Prefix-aware packing (the packed cache-HIT path)
------------------------------------------------
Cache-hit requests co-pack too: each hit segment contributes only its
SUFFIX tokens to the packed forward and attends its cached prefix KV
through a gathered per-segment prefix buffer (position-masked
segment-restricted attention — ``tfm.prefill_packed_with_prefix``). A small
per-candidate cost model chooses between {solo suffix, packed miss, packed
hit}: a candidate joins the batch only when the packed-step JCT estimate
over bucketed forward sizes beats running it sequentially. Prefix sharers
whose shared prefix is ALREADY cached can therefore co-pack (each attends
its own gathered copy); sharers whose prefix is not yet cached still run
sequentially so the later one hits the earlier one's freshly inserted KV
(BatchLLM's global-prefix observation).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.hybrid_prefill import skipped_slots
from repro.core.jct import LinearProxyJCT, PackedShapeJCT, Sample
from repro.core.kv_policy import KVLifecycle, bucket as _bucket
from repro.core.offload import (HostKVStore, OffloadPolicy,
                                TieredPrefixCache)
from repro.core.prefix_cache import PrefixCache, token_chain
from repro.core.scheduler import Request, Scheduler
from repro.models import hybrid as hybrid_model
from repro.models import transformer as tfm
from repro.models.layers import PAD_POS
from repro.models.model import cast_params
from repro.runtime.fault_tolerance import NaNGuard
from repro.runtime.hw import chip_for
from repro.serving.tracing import BatchRecord, JCTCalibrationMonitor, Phase


# Cache blocks one split program cuts from a kept-KV output (a 17k-token
# miss, about 1,073 blocks, takes 17 dispatches). The last call's surplus,
# at most SPLIT_BLOCKS - 1 blocks, is dropped as it returns.
SPLIT_BLOCKS = 64


@dataclasses.dataclass
class EngineConfig:
    policy: str = "srjf_calibrated"
    lam: float = 0.05                 # starvation offset (JCT-sec per wait-sec)
    block_size: int = 16
    cache_capacity_tokens: int = 4096  # prefix-KV budget (profile run output)
                                       # in KV tokens; a hybrid's state
                                       # snapshot is priced at its bytes'
                                       # worth of KV tokens
    kv_keep_tokens: int = 10**9        # suffix discard threshold (per request)
    suffix_buckets: Tuple[int, ...] = (64, 128, 256, 512, 1024, 2048)
    prefix_bucket_blocks: int = 4      # reuse granularity: 4 blocks = 64 tok
                                       # (a hybrid's snapshot stride too)
    pack_token_budget: int = 2048      # prepacking: max COMPUTED tokens/step
    max_pack_requests: int = 16        # prepacking: max segments per step
                                       # (<=1 disables batch formation)
    pack_prefix_budget: int = 4096     # packed-hit path: max gathered prefix
                                       # tokens per step (attended, not
                                       # computed — cheaper than suffix toks)
    prefix_buckets: Tuple[int, ...] = (128, 256, 384, 512, 1024, 2048, 4096)
                                       # per-segment gathered-prefix pad
                                       # ladder: 128-steps below 512 (the
                                       # batched hit attention pays compute
                                       # proportional to pmax, so tight pads
                                       # matter), doubling above (the jit
                                       # key is (S, Nb, smax, pmax, K) and
                                       # batch composition shifts step to
                                       # step — a fine ladder up high would
                                       # recompile in steady state)
    autotune_pack: bool = True         # retune both from the profile() fit
    pack_inflation: float = 2.0        # max anchor-step slowdown autotune
                                       # accepts vs a typical solo step
    shape_cost_model: bool = True      # price batch formation with the
                                       # shape-aware PackedShapeJCT (marginal
                                       # padded-shape cost); False falls back
                                       # to the token-linear proxy on the
                                       # same marginal rule (benchmark arm)
    shape_pad_discount: float = 0.25   # unfitted-prior rent per padded slot,
                                       # as a fraction of the linear proxy's
                                       # per-computed-token rate
    offload: bool = False              # DRAM tier: evicted prefix blocks
                                       # demote to a HostKVStore instead of
                                       # being discarded (paper §9)
    host_cache_bytes: int = 256 << 20  # DRAM tier capacity per instance
    offload_host_bw: Optional[float] = None
                                       # override the OffloadPolicy's link
                                       # bandwidth (bytes/s). None = the
                                       # ChipSpec value, later replaced by
                                       # profile()'s measured bandwidth.
                                       # The worth_restoring economics are
                                       # priced for the TARGET chip, so CPU
                                       # smoke/benchmark runs of reduced
                                       # models pass a large value here to
                                       # force the restore path.


class PrefillOnlyEngine:
    """Single-instance engine over a dense-family model, or a hybrid with a
    published layer pattern (``ModelConfig.layer_types``), on real arrays.

    ``device`` (default: the first device) holds this engine's parameters and
    therefore its forwards and KV: inputs are uncommitted and follow the
    committed parameters. ``chip`` is that device's peak table, which the KV
    tier and admission price against.

    A hybrid keeps two kinds of state in one cache: its attention layers'
    KV in every block, and at every reuse boundary (``prefix_bucket_blocks``
    blocks, a multiple of the SSD chunk) a snapshot of its Mamba layers'
    state in that block beside the KV. A hit resumes from the deepest
    boundary whose block holds one. It runs packs of one: a hybrid allowed
    to pack, or to offload, is refused here."""

    def __init__(self, cfg: ModelConfig, params,
                 ecfg: Optional[EngineConfig] = None, device=None):
        self.stateful = bool(cfg.layer_types)
        if not (self.stateful
                or cfg.family in ("dense", "vlm", "audio", "moe")):
            raise ValueError(f"the engine serves no {cfg.family!r} model "
                             "without a layer pattern")
        ecfg = EngineConfig() if ecfg is None else ecfg
        self.snap_every = (ecfg.block_size * ecfg.prefix_bucket_blocks
                           if self.stateful else 0)
        if self.stateful:
            if ecfg.max_pack_requests > 1:
                raise ValueError(
                    "a hybrid runs packs of one: packed prefill would have "
                    "to reset the Mamba state at each segment; set "
                    "max_pack_requests 1")
            if ecfg.offload:
                raise ValueError("a hybrid's state snapshots have no host "
                                 "tier; set offload False")
            if self.snap_every % cfg.ssm_chunk:
                raise ValueError(
                    f"the reuse step {self.snap_every} is no multiple of the "
                    f"SSD chunk {cfg.ssm_chunk}")
        self.cfg = cfg
        self.device = jax.devices()[0] if device is None else device
        self.chip = chip_for(self.device.platform, self.device.device_kind)
        self.params = jax.device_put(cast_params(params, cfg.dtype),
                                     self.device)
        # per-engine config: a shared default instance would alias mutable
        # state (autotune) across every engine in a pool
        self.ecfg = ecfg
        # Guards queue / cache / results / jct_model. The engine is driven by
        # ONE worker thread (step) while router/server threads concurrently
        # submit, cancel, shed, and probe backlog — the forward itself runs
        # outside the lock so probes never wait on compute.
        self.lock = threading.RLock()
        # KV keep/discard has ONE owner: every keep-budget / residency /
        # insert-bound decision in this file asks self.kv (kv_policy).
        self.kv = KVLifecycle(block_size=ecfg.block_size,
                              kv_keep_tokens=ecfg.kv_keep_tokens,
                              buckets=ecfg.suffix_buckets,
                              state_stride=self.snap_every)
        # capacity one snapshot occupies beside its block, in KV blocks
        self.snapshot_units = (-(-cfg.state_bytes() // max(
            1, ecfg.block_size * cfg.kv_bytes_per_token()))
            if self.stateful else 0)
        if ecfg.offload:
            # hierarchical KV memory: device blocks demote to host DRAM on
            # eviction, restore on match when cheaper than recompute
            self.cache: PrefixCache = TieredPrefixCache(
                ecfg.cache_capacity_tokens // ecfg.block_size,
                ecfg.block_size,
                host_store=HostKVStore(ecfg.host_cache_bytes), cfg=cfg,
                policy=OffloadPolicy(self.chip,
                                     host_bw=ecfg.offload_host_bw))
        else:
            self.cache = PrefixCache(
                ecfg.cache_capacity_tokens // ecfg.block_size,
                ecfg.block_size)
        self.jct_model = LinearProxyJCT()
        # shape-aware step pricing (ISSUE 10): batch formation admits by
        # marginal padded-shape cost; routers/admission/Algorithm-1 keep the
        # per-request linear proxy on the miss-token axis
        self.shape_jct = PackedShapeJCT(
            fallback=self.jct_model, pad_discount=ecfg.shape_pad_discount)
        # usable_prefix hook: Algorithm-1 scores must price requests against
        # the prefix a forward would actually reuse, matching the hit-aware
        # predict_jct/pending_jct/shed probes — not the raw token match
        self.scheduler = Scheduler(ecfg.policy, self.jct_model, ecfg.lam,
                                   usable_prefix=self._usable_prefix_len)
        self.queue: List[Request] = []
        self.results: Dict[int, Dict] = {}
        self._fresh_fns: Dict[Tuple[int, int], callable] = {}
        self._suffix_fns: Dict[Tuple[int, int, int], callable] = {}
        self._packed_fns: Dict[Tuple[int, int], callable] = {}
        self._packed_hit_fns: Dict[Tuple[int, int, int], callable] = {}
        self._split_fns: Dict[Tuple, callable] = {}
        self._snap_fns: Dict[Tuple, callable] = {}
        self._last_step_ids: List[int] = []    # all requests served by the
                                               # most recent step()
        self._inflight: List[int] = []         # popped by step(), not yet in
                                               # results (crash accounting)
        self._inflight_pred = 0.0              # predicted cost of that batch
        self._inflight_t0 = 0.0                # and when it started
        self.steps = 0
        self.hit_tokens = 0
        self.total_tokens = 0
        self.packed_steps = 0          # steps that executed >1 request
        self.packed_requests = 0       # requests served via prepacking
        self.packed_hit_requests = 0   # ...of which rode a cached prefix
        self.padded_slots = 0          # bucketed forward slots actually paid
        self.pack_skew_splits = 0      # packs closed early because the best
                                       # remaining candidate's padding
                                       # externality exceeded its benefit
        self.kv_insert_blocks = 0      # cache blocks cut from fresh KV
        self.kv_insert_programs = 0    # split programs dispatched for them
        self._formed_cost = 0.0        # shape-priced cost of the last pack
        self._step_compiled = False    # step hit a fresh jit shape
        # result validation: a forward can emit non-finite logits (bad
        # checkpoint cast, accelerator fault) — such results are flagged
        # "corrupt" so the serving layer quarantines them instead of
        # delivering NaN scores; consecutive corruption advises a reload
        # via the training-side NaNGuard policy
        self.result_guard = NaNGuard(limit=3)
        self.nonfinite_results = 0
        # brownout hook (serving): when degraded, cache-HIT requests skip
        # the batched gathered-prefix path and run the cheap solo-suffix
        # path instead — per-step cost variance collapses under overload
        self.degraded = False
        # observability: always-on bounded per-step BatchRecords + online
        # JCT-calibration monitoring (residuals per bucket class, drift ->
        # forced refit). Prometheus/trace export activates via
        # bind_telemetry(); unbound, the only cost is the ring append.
        self.batch_records: "deque[BatchRecord]" = deque(maxlen=256)
        self.jct_monitor = JCTCalibrationMonitor(
            self.jct_model, buckets=ecfg.suffix_buckets,
            shape_model=self.shape_jct)
        self.metrics = None
        self.instance_name = ""
        self.tracer = None
        self._last_jit: Tuple[str, Tuple, bool] = ("", (), False)
        self._last_shape: Dict[str, int] = {}
        self._phases: Dict[str, float] = {}    # host seconds per phase of
                                               # the current step (Phase)
        self._matched_tokens = 0               # this step's cached prefix
        self._resumed_tokens = 0               # and reused prefix tokens
        self._skipped_tokens = 0               # and pad rows not computed

    # ---- profile run (paper §3.1) ------------------------------------------
    def profile(self, lengths: Sequence[int] = (64, 128, 256, 512)) -> float:
        """Measure jct(n_input, 0) on this host, fit the linear proxy."""
        samples: List[Sample] = []
        rng = np.random.default_rng(0)
        for n in lengths:
            toks = rng.integers(0, self.cfg.vocab_size, size=n).tolist()
            self._run_fresh(toks)            # warm-up: exclude compile time
            for _ in range(2):               # steady-state samples
                t0 = time.perf_counter()
                logits, _, _ = self._run_fresh(toks)
                jax.block_until_ready(logits)
                samples.append((n, 0, time.perf_counter() - t0))
        self.jct_model.fit(samples)
        if (isinstance(self.cache, TieredPrefixCache)
                and self.ecfg.offload_host_bw is None):
            # override the ChipSpec host-bandwidth constant with THIS host's
            # measured device<->host copy rate: worth_restoring's break-even
            # then prices transfers the way this machine actually pays them.
            # An explicit offload_host_bw config wins over the measurement.
            self.cache.policy.host_bw = self._measure_host_bw()
        if self.ecfg.autotune_pack:
            self.autotune_packing(ref_len=max(lengths))
        return self.jct_model.pearson_r

    def _measure_host_bw(self, nbytes: int = 8 << 20) -> float:
        """Measured device->host->device round-trip bandwidth (bytes/s)."""
        arr = jax.device_put(np.zeros((nbytes // 4,), np.float32),
                             self.device)
        jax.block_until_ready(arr)
        t0 = time.perf_counter()
        host = np.asarray(arr)                       # device -> host
        back = jax.device_put(host, self.device)     # host -> device
        jax.block_until_ready(back)
        dt = max(time.perf_counter() - t0, 1e-9)
        return 2.0 * nbytes / dt

    def autotune_packing(self, ref_len: int) -> Tuple[int, int]:
        """Tune ``pack_token_budget`` / ``max_pack_requests`` from the fitted
        JCT curve instead of fixed defaults (ROADMAP follow-up).

        Packing trades anchor latency for throughput: a packed step costs
        jct(total tokens) instead of jct(anchor tokens). Accept that trade up
        to ``pack_inflation``x the cost of a typical solo step (a ``ref_len``
        request — the largest profiled length): with jct = a*S + b the budget
        solves a*S + b <= inflation * (a*ref + b), so hosts with a large
        fixed overhead b relative to per-token cost a (where amortizing b is
        the whole win) get a proportionally larger budget. The request cap
        follows as budget / smallest-bucket, i.e. the most segments a full
        budget could plausibly hold.
        """
        m, ecfg = self.jct_model, self.ecfg
        if m.a <= 0 or self.stateful:           # a hybrid packs nothing
            return ecfg.pack_token_budget, ecfg.max_pack_requests
        max_step = ecfg.pack_inflation * m.predict(ref_len)
        floor = _bucket(ref_len, ecfg.suffix_buckets)
        budget = max([floor] + [s for s in ecfg.suffix_buckets
                                if m.predict(s) <= max_step])
        n_max = int(np.clip(budget // max(1, ecfg.suffix_buckets[0]), 1, 64))
        # gathered prefix tokens are attended, not computed — the per-token
        # cost the proxy fits barely sees them, so the hit path can carry a
        # proportionally larger prefix buffer than its computed budget
        self.ecfg = dataclasses.replace(ecfg, pack_token_budget=budget,
                                        max_pack_requests=n_max,
                                        pack_prefix_budget=max(
                                            ecfg.pack_prefix_budget,
                                            2 * budget))
        return budget, n_max

    # ---- request lifecycle ---------------------------------------------------
    def submit(self, tokens: Sequence[int],
               allowed_tokens: Optional[Sequence[int]] = None,
               user_id: Optional[str] = None, now: Optional[float] = None,
               deadline: Optional[float] = None,
               chain: Optional[Tuple[int, ...]] = None) -> int:
        now = time.perf_counter() if now is None else now
        r = Request(n_input=len(tokens), arrival=now,
                    chain=(token_chain(tokens, self.ecfg.block_size)
                           if chain is None else chain),
                    tokens=list(tokens), user_id=user_id,
                    allowed_tokens=tuple(allowed_tokens) if allowed_tokens else None,
                    deadline=deadline)
        with self.lock:
            # probe_len: serveable prefix incl. the host tier, restore-free
            r.n_cached_at_arrival = self.cache.probe_len(r.chain)
            self.queue.append(r)
        return r.req_id

    def cancel(self, req_id: int) -> Optional[Request]:
        """Remove a QUEUED request (no effect once executing). Returns the
        removed request, or None if it was not waiting here."""
        with self.lock:
            for i, r in enumerate(self.queue):
                if r.req_id == req_id:
                    return self.queue.pop(i)
        return None

    def shed_expired(self, now: Optional[float] = None) -> List[Request]:
        """Pop queued requests that cannot meet their deadline anymore:
        even starting RIGHT NOW, now + predicted JCT > deadline. Shedding
        them early converts a guaranteed tail-latency blowup into a cheap
        typed rejection (admission control's in-queue half)."""
        now = time.perf_counter() if now is None else now
        shed: List[Request] = []
        with self.lock:
            keep = []
            for r in self.queue:
                if r.deadline is not None and (
                        now + self.jct_model.predict(
                            r.n_input, self._usable_prefix_len(
                                r.n_input,
                                self.cache.probe_blocks(r.chain)))
                        > r.deadline):
                    shed.append(r)
                else:
                    keep.append(r)
            if shed:
                self.queue[:] = keep
        return shed

    def pending_jct(self, now: Optional[float] = None) -> float:
        """Predicted seconds of queued work PLUS the predicted remainder of
        the batch executing right now — the backlog signal JCT-aware routing
        ranks instances by. Only meaningful because prefill-only JCT is
        precisely predictable.

        Queued requests are scored against their ARRIVAL-time cache match
        (already computed by submit), not re-walked against the live cache:
        the router calls this for every instance on every arrival, and an
        O(queue x chain) walk under the engine lock would contend with the
        worker exactly when routing matters most. The estimate only errs
        conservative (the cache can have warmed since arrival, never
        cooled for a queued request's own prefix).

        Hit-aware: the raw match is first bucketed down to the prefix the
        engine would actually REUSE (``_usable_prefix_len``), so the backlog
        the router ranks by reflects real computed-token cost, not an
        optimistic token-granular match."""
        now = time.perf_counter() if now is None else now
        bs = self.ecfg.block_size
        with self.lock:
            queued = sum(
                self.jct_model.predict(
                    r.n_input, self._usable_prefix_len(
                        r.n_input, r.n_cached_at_arrival // bs))
                for r in self.queue)
            running = 0.0
            if self._inflight:
                running = max(0.0, self._inflight_pred
                              - (now - self._inflight_t0))
            return queued + running

    def predict_jct(self, n_input: int, chain: Tuple[int, ...] = ()) -> float:
        """Predicted JCT of a PROSPECTIVE request given this instance's
        cache state (router's per-instance cost probe). Hit-aware: predicts
        against the reuse-granularity prefix the engine would actually use,
        never the raw (token-granular, whole-request-consuming) match."""
        with self.lock:
            return self.jct_model.predict(
                n_input, self._usable_prefix_len(
                    n_input, self.cache.probe_blocks(chain)))

    def cached_prefix_len(self, chain: Tuple[int, ...]) -> int:
        with self.lock:
            return self.cache.probe_len(chain)

    def probe(self, n_input: int,
              chain: Tuple[int, ...] = ()) -> Tuple[float, float, int]:
        """All three router probes — ``(pending_jct, predict_jct,
        cached_prefix_len)`` — in ONE lock acquisition. The RPC worker
        plane serves a router scan as a single round trip through this
        instead of three, and in-process callers get the same atomicity
        (the three values describe one consistent cache/queue state)."""
        with self.lock:
            return (self.pending_jct(), self.predict_jct(n_input, chain),
                    self.cache.probe_len(chain))

    @property
    def last_step_ids(self) -> List[int]:
        return list(self._last_step_ids)

    def inflight_snapshot(self) -> Tuple[List[int], float, float]:
        """(in-flight request ids, predicted batch JCT, start timestamp) —
        the serving watchdog's hang probe. A batch still in flight past
        ``factor x`` the predicted JCT is provably wedged (prefill-only JCT
        is precisely predictable), so this triple is all a watchdog needs.

        A step that triggered a fresh jit compile reports EMPTY: compile
        time is unbounded and outside the JCT model (the same reason step()
        excludes compile steps from the fit), so "provably wedged" does not
        hold — the deadline applies from the first warm execution of a
        shape on."""
        with self.lock:
            if self._step_compiled:
                return [], 0.0, 0.0
            return (list(self._inflight), self._inflight_pred,
                    self._inflight_t0)

    def bind_telemetry(self, metrics=None, instance: str = "",
                       tracer=None) -> None:
        """Attach the serving registry and/or a SpanTracer. The JCT monitor
        exports coefficient gauges immediately so a scrape before the first
        warm step still sees the profile() fit."""
        self.metrics = metrics
        self.instance_name = instance
        self.tracer = tracer
        self.jct_monitor.bind(metrics, instance)

    def set_degraded(self, flag: bool) -> None:
        """Brownout level >=2 hook: disable hit co-packing's batched
        gathered-prefix forward (hits run the cheap solo-suffix path,
        misses still co-pack). Takes effect at the next batch formation."""
        with self.lock:
            self.degraded = bool(flag)

    # ---- DRAM offload tier (paper §9) ---------------------------------------
    def _match_restoring(self, chain: Tuple[int, ...],
                         rid: Optional[int] = None) -> int:
        """``match_blocks(touch=True)`` with restore observability: on the
        tiered cache a match can pull blocks back from the host store —
        time it, count it, emit the ``restore`` span + series. Execution
        path only; call under the engine lock."""
        c = self.cache
        if not isinstance(c, TieredPrefixCache):
            return c.match_blocks(chain, touch=True)
        r0, b0 = c.restored_blocks, c.host.restore_bytes
        t0 = time.perf_counter()
        matched = c.match_blocks(chain, now=t0, touch=True)
        blocks = c.restored_blocks - r0
        if blocks:
            self._note_tier("restore", rid, blocks,
                            c.host.restore_bytes - b0, t0,
                            time.perf_counter())
        return matched

    def _note_tier(self, kind: str, rid: Optional[int], blocks: int,
                   nbytes: int, t0: float, t1: float) -> None:
        """Export one restore/prefetch episode as Prometheus series and (when
        a request id is known) a SpanTracer phase."""
        m, inst = self.metrics, self.instance_name
        if m is not None:
            m.counter(f"kv_{kind}_blocks", inst,
                      help=f"KV blocks moved host->device by {kind}").inc(
                blocks)
            m.counter(f"kv_{kind}_bytes", inst).inc(nbytes)
            m.histogram(f"kv_{kind}_seconds", inst,
                        help=f"wall seconds per {kind} episode").observe(
                t1 - t0)
        tr = self.tracer
        if tr is not None and rid is not None:
            tr.span_rid(rid, kind, t0, t1, instance=inst,
                        blocks=blocks, bytes=int(nbytes))

    def restore_estimate(self, chain: Tuple[int, ...]) -> Dict[str, float]:
        """Restorable host-tier continuation of ``chain`` and its priced
        transfer time — admission folds ``restore_s`` into the JCT bound,
        the router-time prefetch decides off ``blocks``. Zeros on an
        un-tiered engine."""
        c = self.cache
        if not isinstance(c, TieredPrefixCache):
            return {"device_blocks": 0, "blocks": 0, "bytes": 0,
                    "restore_s": 0.0}
        with self.lock:
            return c.restore_estimate(chain)

    def prefetch_prefix(self, chain: Tuple[int, ...],
                        rid: Optional[int] = None) -> int:
        """Async host->device prefetch of ``chain``'s restorable
        continuation, triggered at routing time (the router knows the
        usable prefix before the forward runs). Returns the block count
        scheduled (0 = nothing restorable / no tier). The transfer runs on
        a daemon thread: restore into the device cache under the lock, then
        materialize the payloads as device arrays OUTSIDE the lock so the
        execute-path concatenate hits device-resident KV."""
        c = self.cache
        if not isinstance(c, TieredPrefixCache):
            return 0
        with self.lock:
            est = c.restore_estimate(chain)
        if not est["blocks"]:
            return 0
        threading.Thread(target=self._prefetch_worker,
                         args=(tuple(chain), rid),
                         daemon=True, name="kv-prefetch").start()
        return int(est["blocks"])

    def _prefetch_worker(self, chain: Tuple[int, ...],
                         rid: Optional[int]) -> None:
        c = self.cache
        t0 = time.perf_counter()
        with self.lock:
            r0, b0 = c.restored_blocks, c.host.restore_bytes
            matched = c.match_blocks(chain, now=t0, touch=True)
            blocks = c.restored_blocks - r0
            nbytes = c.host.restore_bytes - b0
            hs = chain[matched - blocks:matched] if blocks else ()
            host_payloads = [(h, c.blocks[h].payload) for h in hs
                             if h in c.blocks
                             and c.blocks[h].payload is not None]
        if not blocks:
            return
        # host -> device outside the lock (the copy is the slow part)
        dev = [(h, tuple(jax.device_put(p, self.device) for p in payload))
               for h, payload in host_payloads]
        for _, payload in dev:
            jax.block_until_ready(payload)
        with self.lock:
            for h, payload in dev:
                blk = c.blocks.get(h)
                # only upgrade a still-host-resident numpy payload — never
                # clobber KV a concurrent insert refreshed on device
                if blk is not None and blk.payload is not None and isinstance(
                        blk.payload[0], np.ndarray):
                    blk.payload = payload
        self._note_tier("prefetch", rid, blocks, nbytes, t0,
                        time.perf_counter())

    def step(self) -> Optional[int]:
        """One scheduling step: pick (Algorithm 1), form a packed batch,
        prefill, cache, score. Returns the anchor request's id.

        A step with queued work runs inside the profiler span
        ``engine.step``, and each of its host phases (form_batch,
        cache_match, kv_gather, dispatch, device_wait, kv_insert,
        state_insert (a hybrid's snapshots), score, record) is a
        ``Phase``: a leaf span of its own and its seconds in the step's
        ``BatchRecord.phases``. The worker polls ``step()``, so
        an empty queue returns before any span opens."""
        if not self.queue:
            return None
        self._phases = {}
        self._matched_tokens = self._resumed_tokens = self._skipped_tokens = 0
        with jax.profiler.TraceAnnotation("engine.step"):
            return self._step()

    def _phase(self, name: str) -> Phase:
        return Phase(self._phases, name)

    def _step(self) -> Optional[int]:
        now = time.perf_counter()
        with self._phase("form_batch"):
            batch = self._form_batch(now)
        if batch is None:
            return None
        for r in batch:
            r.start_time = now
        with self.lock:
            self._inflight = [r.req_id for r in batch]
            # the shape-priced cost of the formed pack — the watchdog
            # deadline and BatchRecord.predicted_jct consume the same number
            # batch formation admitted against
            self._inflight_pred = self._formed_cost
            self._inflight_t0 = now
        self._step_compiled = False
        padded0 = self.padded_slots
        if len(batch) == 1:
            r = batch[0]
            logits = self._execute(r)
            # async dispatch: sync before timestamping, or the JCT model
            # observes launch latency instead of compute time
            with self._phase("device_wait"):
                jax.block_until_ready(logits)
            done = r.finish_time = time.perf_counter()
            with self._phase("score"), self.lock:
                self.results[r.req_id] = self._score(logits, r)
                # steps that compiled a fresh shape are NOT JCT samples — a
                # multi-second jit compile recorded as serving cost wrecks the
                # refit (profile() excludes compiles the same way via warm-up)
                if not self._step_compiled:
                    self.jct_model.observe(r.n_input, r.n_cached_at_start,
                                           r.finish_time - now)
        else:
            logits = self._execute_packed(batch)
            with self._phase("device_wait"):
                jax.block_until_ready(logits)
            done = time.perf_counter()
            with self._phase("score"), self.lock:
                for n, r in enumerate(batch):
                    r.finish_time = done
                    self.results[r.req_id] = self._score(logits[n:n + 1], r)
                # packed cost is a function of COMPUTED tokens — misses
                # compute all their tokens, hits only their suffixes: report
                # it on the same miss-token axis Algorithm 1 scores with, so
                # mixed hit/miss batches don't skew the fit that
                # autotune_packing and admission feasibility consume
                if not self._step_compiled:
                    self.jct_model.observe(
                        sum(r.n_input - r.n_cached_at_start for r in batch),
                        0, done - now)
            self.packed_steps += 1
            self.packed_requests += len(batch)
            self.packed_hit_requests += sum(
                1 for r in batch if r.n_cached_at_start > 0)
        self.steps += 1
        self._last_step_ids = [r.req_id for r in batch]
        with self._phase("record"):
            self._record_step(batch, now, done, time.perf_counter(), padded0)
        with self.lock:
            self._inflight = []
            self._inflight_pred = 0.0
        return batch[0].req_id

    def _record_step(self, batch: List[Request], t0: float, t_done: float,
                     t_scored: float, padded0: int) -> None:
        """Observability epilogue of step(): BatchRecord into the ring, JCT
        calibration sample (warm steps only), per-request trace spans. The
        record shares the step's phase dict, so its ``record`` entry (this
        epilogue's own cost) lands when the phase closes."""
        pred = self._inflight_pred
        computed = sum(r.n_input - r.n_cached_at_start for r in batch)
        kind = ("solo" if len(batch) == 1
                else "hit" if any(r.n_cached_at_start for r in batch)
                else "miss")
        path, key, _ = self._last_jit
        shape = self._last_shape
        rec = BatchRecord(
            step=self.steps, ts=t_done, instance=self.instance_name,
            kind=kind, n_requests=len(batch),
            req_ids=tuple(r.req_id for r in batch),
            computed_tokens=computed,
            padded_tokens=self.padded_slots - padded0,
            S=shape.get("S", 0), Nb=shape.get("Nb", 0),
            smax=shape.get("smax", 0), pmax=shape.get("pmax", 0),
            K=shape.get("K", 0), jit_path=path, jit_key=key,
            compiled=self._step_compiled, predicted_jct=pred,
            wall=t_done - t0, phases=self._phases,
            matched_tokens=self._matched_tokens,
            resumed_tokens=self._resumed_tokens,
            skipped_tokens=self._skipped_tokens)
        self.batch_records.append(rec)
        # compile steps are excluded from calibration for the same reason
        # they are excluded from the JCT fit: compile time is unbounded and
        # not a prediction error
        if not self._step_compiled:
            self.jct_monitor.observe(pred, t_done - t0, computed, kind=kind)
            # the shape model learns from the realized (shape, wall) pair —
            # the same BatchRecord axes formation priced the pack on
            self.shape_jct.observe(computed, rec.S, rec.Nb, rec.smax,
                                   rec.pmax, rec.wall)
        m = self.metrics
        if m is not None:
            m.histogram("padding_waste", self.instance_name).observe(
                rec.padding_waste)
            m.counter("padded_slots", self.instance_name).inc(
                rec.padded_tokens)
            m.counter(f"pack_{kind}_steps", self.instance_name).inc()
            m.histogram("batch_wall_seconds", self.instance_name).observe(
                rec.wall)
            if isinstance(self.cache, TieredPrefixCache):
                hs = self.cache.host.stats()
                m.gauge("host_kv_used_bytes", self.instance_name,
                        help="DRAM offload tier occupancy").set(
                    hs["used_bytes"])
                m.gauge("kv_offload_blocks", self.instance_name,
                        help="KV blocks demoted device->host (cumulative)"
                        ).set(hs["offloads"])
        tr = self.tracer
        if tr is None:
            return
        tr.record_batch(rec)
        inst = self.instance_name
        peers = [r.req_id for r in batch]
        for r in batch:
            tr.span_rid(r.req_id, "queue", r.arrival, t0, instance=inst)
            tr.span_rid(r.req_id, "execute", t0, t_done, instance=inst,
                        pack=kind, compiled=self._step_compiled,
                        jit_path=path)
            tr.span_rid(r.req_id, "score", t_done, t_scored, instance=inst)
            tr.event_rid(r.req_id, "batch", kind=kind, step=self.steps,
                         peers=[p for p in peers if p != r.req_id],
                         predicted_jct=pred, computed_tokens=computed,
                         n_cached=r.n_cached_at_start)
            if self._step_compiled:
                tr.event_rid(r.req_id, "jit_compile", path=path,
                             key=list(key))

    # ---- batch formation (prepacking) ---------------------------------------
    def _usable_prefix_len(self, n_input: int, matched_blocks: int) -> int:
        """Bucketed prefix-reuse length given a raw cache match in blocks
        (granularity ``prefix_bucket_blocks``; >=1 fresh token guaranteed —
        the last token's logits must be computed). Static arithmetic shared
        by execution and by the hit-aware routing/shedding probes, so
        predictions match what a forward would actually reuse."""
        bs = self.ecfg.block_size
        gran = self.ecfg.prefix_bucket_blocks
        prefix_len = (matched_blocks // gran) * gran * bs
        if prefix_len >= n_input:
            prefix_len = max(0, ((n_input - 1) // (gran * bs)) * gran * bs)
        return prefix_len

    def _usable_prefix(self, r: Request, touch: bool = False) -> int:
        """Bucketed prefix-reuse length for ``r`` against the current cache.
        Non-touch callers (batch formation, inflight pricing) get the
        side-effect-free probe — on the tiered cache an eager match here
        would restore host blocks for requests that may never run."""
        if touch:
            matched = self.cache.match_blocks(r.chain, touch=True)
        else:
            matched = self.cache.probe_blocks(r.chain)
        return self._usable_prefix_len(r.n_input, matched)

    def _pack_shape(self, rows: List[Tuple[int, int]]) -> Tuple[
            int, int, int, int, int]:
        """Realized step shape ``(S, Nb, smax, pmax, pad_slots)`` for a pack
        of ``rows`` = [(suffix_tokens, usable_prefix), ...].

        Mirrors ``_execute_packed``'s layout arithmetic exactly so formation
        prices the same shape execution will pay. A single row prices the
        solo path: S = bucketed suffix, exact prefix buffer (Nb/smax = 0 by
        the ``step_features`` canonicalization). ``pad_slots`` counts the
        padded-but-dead slots a candidate's admission is charged for:
        Σ(pmax−pref_i) + Σ(smax−suf_i) over the REAL rows packed, bucket
        slack solo. The pow2 ghost rows (Nb−N) are deliberately not charged
        here: they are a step-function layout artifact that would make
        marginal admission oscillate at row-power boundaries — the fitted
        model prices them from data (Nb is in its feature basis).
        """
        ecfg = self.ecfg
        if len(rows) == 1:
            suffix, pref = rows[0]
            S = _bucket(suffix, ecfg.suffix_buckets)
            return S, 0, 0, pref, S - suffix
        suffixes = [s for s, _ in rows]
        total = sum(suffixes)
        S = _bucket(total, ecfg.suffix_buckets)
        P_max = max(p for _, p in rows)
        pmax = _bucket(P_max, ecfg.prefix_buckets) if P_max else 0
        Nb = 1
        while Nb < len(rows):
            Nb *= 2
        smax = _bucket(max(suffixes), (32, 48) + ecfg.suffix_buckets)
        if not pmax:
            # all-miss pack executes as ONE flat (1, S) sequence — no row
            # padding; only the bucket slack is dead
            return S, Nb, smax, 0, S - total
        pad = (sum(pmax - p for _, p in rows)
               + sum(smax - s for s in suffixes))
        return S, Nb, smax, pmax, pad

    def _pack_cost(self, rows: List[Tuple[int, int]]) -> float:
        """Predicted wall seconds for one step over ``rows``.

        ``shape_cost_model=False`` keeps the legacy token-linear pricing
        (cost depends only on bucketed computed tokens) — the marginal admit
        rule then reduces exactly to the old
        ``jct(bucket(total+suffix)) <= jct(bucket(total)) + jct(bucket(suffix))``
        inequality, which is the benchmark's comparison arm.
        """
        computed = sum(s for s, _ in rows)
        if not self.ecfg.shape_cost_model:
            return self.jct_model.predict(
                _bucket(computed, self.ecfg.suffix_buckets))
        S, Nb, smax, pmax, pad = self._pack_shape(rows)
        return self.shape_jct.predict(computed, S, Nb, smax, pmax,
                                      pad_slots=pad)

    def _form_batch(self, now: float) -> Optional[List[Request]]:
        """Algorithm 1 pick + marginal-cost backfill (shape-priced).

        The anchor is exactly the scheduler's pick, so SRJF-calibrated order
        is preserved. Backfill then grows the pack greedily: every queued
        candidate is priced by its MARGINAL shape-aware batch cost
        ``cost(pack + r) − cost(pack)`` against its solo cost, and the
        scheduler's ``pick_backfill`` admits the candidate with the largest
        benefit ``solo(r) − marginal(r)``. Cache misses contribute their
        full length, cache hits only their suffix — hit segments attend
        their cached prefix KV through the gathered prefix buffer, so hit
        anchors are backfillable and hit candidates co-pack.

        ``cost`` is the PackedShapeJCT prediction over the realized padded
        shape (S, Nb, smax, pmax): a long-prefix or long-suffix row that
        re-prices every already-admitted row's padding shows up as a large
        marginal and is rejected by PRICE — this replaces the old
        ``pb > 2*pmax_b`` / ``pref > 4*(total+suffix)`` heuristic blowup
        gates. When the best remaining candidate's benefit is negative the
        pack CLOSES (skew split, counted in ``pack_skew_splits``): the
        rejected candidates stay queued and seed the next step's low-skew
        pack instead of inflating this one.

        Hard gates (not priced): computed tokens <= ``pack_token_budget``;
        gathered prefix tokens <= ``pack_prefix_budget``; brownout skips hit
        gathers. Requests sharing a prefix root (same first hash-chain
        block) co-pack ONLY when both sides already hit the cache (each
        attends its own gathered copy of the shared KV). A miss sharing a
        root still runs sequentially, so the later request hits the earlier
        one's freshly inserted KV — that reuse beats any packing win
        (BatchLLM's global-prefix observation).
        """
        with self.lock:
            i = self.scheduler.pick(self.queue, self.cache, now)
            if i is None:
                return None
            anchor = self.queue.pop(i)
            batch = [anchor]
            ecfg = self.ecfg
            pref_a = self._usable_prefix(anchor)
            rows = [(anchor.n_input - pref_a, pref_a)]
            if (ecfg.max_pack_requests <= 1 or ecfg.pack_token_budget <= 0
                    or not self.queue or (self.degraded and pref_a)):
                # brownout: a hit anchor runs the cheap solo-suffix path
                # instead of anchoring a batched gathered-prefix forward
                self._formed_cost = self._pack_cost(rows)
                return batch
            total = rows[0][0]                     # computed suffix tokens
            pref_total = pref_a
            hit_roots = ({anchor.chain[0]: pref_a > 0} if anchor.chain
                         else {})
            # one cache walk per candidate (the same O(chain) walk pick()
            # already paid this step) — suffix lengths drive the budget
            # gates and the shape pricing, so they must be known up front
            cands = [(r, self._usable_prefix(r)) for r in self.queue]
            pack_cost = self._pack_cost(rows)

            def benefit(r: Request, pref: int) -> Optional[float]:
                if self.degraded and pref:
                    return None    # brownout: no batched hit gather
                suffix = r.n_input - pref
                if total + suffix > ecfg.pack_token_budget:
                    return None
                if pref and pref_total + pref > ecfg.pack_prefix_budget:
                    return None
                root = r.chain[0] if r.chain else None
                if root is not None and root in hit_roots and not (
                        hit_roots[root] and pref > 0):
                    return None
                marginal = self._pack_cost(rows + [(suffix, pref)]) - pack_cost
                return self._pack_cost([(suffix, pref)]) - marginal

            while len(batch) < ecfg.max_pack_requests and cands:
                j = self.scheduler.pick_backfill(cands, benefit)
                if j is None:
                    break
                r, pref = cands[j]
                if benefit(r, pref) < 0:
                    # the BEST remaining candidate would cost more in this
                    # pack than solo: its padding externality on admitted
                    # rows exceeds the co-packing gain — close the pack
                    self.pack_skew_splits += 1
                    break
                cands.pop(j)
                batch.append(r)
                rows.append((r.n_input - pref, pref))
                total += r.n_input - pref
                pref_total += pref
                pack_cost = self._pack_cost(rows)
                root = r.chain[0] if r.chain else None
                if root is not None:
                    hit_roots.setdefault(root, pref > 0)
            self._formed_cost = pack_cost
            for r in batch[1:]:
                self.queue.remove(r)
            return batch

    def run_until_drained(self) -> List[int]:
        """Serve until the queue is empty; returns one id per served request
        in completion order (a packed step contributes its whole batch,
        anchor first)."""
        done = []
        while self.queue:
            if self.step() is not None:
                done.extend(self._last_step_ids)
        return done

    # ---- execution -----------------------------------------------------------
    def _execute(self, r: Request) -> jax.Array:
        bs = self.ecfg.block_size
        state0 = None
        # cache probe + pin under the lock; the forward itself runs outside
        # it so router/admission probes never block on compute
        with self.lock:
            with self._phase("cache_match"):
                matched = self._match_restoring(r.chain, rid=r.req_id)
                prefix_len = self._usable_prefix_len(r.n_input, matched)
                if self.stateful:
                    prefix_len = self._snapshot_prefix(r.chain, prefix_len)
                use_blocks = prefix_len // bs
                r.n_cached_at_start = prefix_len
                self._matched_tokens += matched * bs
                self._resumed_tokens += prefix_len
                self.hit_tokens += prefix_len
                self.total_tokens += r.n_input
                self.padded_slots += prefix_len + _bucket(
                    r.n_input - prefix_len, self.ecfg.suffix_buckets)
                keep = self.kv.keep(r.n_input)
                # chain already resident past the keep bound: the insert
                # below would only re-slice and re-touch existing blocks —
                # skip it (the match walk above refreshed their LRU standing)
                resident = self.kv.resident(matched, r.n_input)
                if prefix_len:
                    self.cache.pin(r.chain, use_blocks)
                    payloads = self.cache.match_payloads(
                        r.chain)[:use_blocks]
            if prefix_len:
                with self._phase("kv_gather"):
                    pk = jnp.concatenate([p[0] for p in payloads], axis=2)
                    pv = jnp.concatenate([p[1] for p in payloads], axis=2)
                    if self.stateful:
                        # the boundary block's snapshot: (ssm, conv)
                        state0 = {"ssm": payloads[-1][2],
                                  "conv": payloads[-1][3]}
        with self._phase("dispatch"):
            if prefix_len == 0:
                logits, new_kv, n_new = self._run_fresh(r.tokens, keep)
                kv_from = 0
            else:
                logits, new_kv, n_new = self._run_suffix(
                    r.tokens[prefix_len:], pk, pv, prefix_len, keep, state0)
                kv_from = prefix_len
        # split fresh KV into block payloads and insert (suffix discard:
        # only up to ``keep`` tokens total)
        with self._phase("kv_insert"), self.lock:
            if prefix_len:
                self.cache.unpin(r.chain, use_blocks)
            if not resident:
                n_insertable = self.kv.insertable_tokens(keep, kv_from, n_new)
                n_blocks_new = n_insertable // bs
                payloads_all = (
                    self.cache.match_payloads(r.chain)[:use_blocks]
                    + self._split_blocks(new_kv, 0, n_blocks_new))
                if not self.stateful:
                    self.cache.insert(r.chain, kv_from + n_blocks_new * bs,
                                      now=time.perf_counter(),
                                      payloads=payloads_all)
        if self.stateful and not resident:
            with self._phase("state_insert"), self.lock:
                units = self._attach_snapshots(new_kv, payloads_all,
                                               use_blocks, n_blocks_new)
                self.cache.insert(r.chain, kv_from + n_blocks_new * bs,
                                  now=time.perf_counter(),
                                  payloads=payloads_all, units=units)
        return logits

    def _snapshot_prefix(self, chain: Tuple[int, ...],
                         prefix_len: int) -> int:
        """The deepest reuse boundary at or below ``prefix_len`` whose cache
        block holds a state snapshot (blocks are resident: the caller
        matched them). Snapshots leave with their blocks, so this is
        ``prefix_len`` itself unless a boundary block lacks one."""
        bs = self.ecfg.block_size
        while prefix_len:
            blk = self.cache.blocks.get(chain[prefix_len // bs - 1])
            if blk is not None and blk.payload is not None \
                    and len(blk.payload) == 4:
                break
            prefix_len -= self.snap_every
        return prefix_len

    def _attach_snapshots(self, aux: Dict, payloads: List[Tuple],
                          first: int, n_blocks: int) -> List[int]:
        """Put each kept snapshot of a forward's ``aux`` beside the KV of
        the block that ends on its boundary: ``payloads[first:]`` are the
        ``n_blocks`` fresh blocks, which start on a boundary. Returns each
        block's capacity units for ``PrefixCache.insert``."""
        units = [1] * len(payloads)
        n = self.kv.snapshots_kept(n_blocks * self.ecfg.block_size)
        if not n:
            return units
        ssm, conv = aux["ssm"], aux["conv"]
        key = (ssm.shape, ssm.dtype, conv.shape, conv.dtype)
        if key not in self._snap_fns:
            self._step_compiled = True

            @jax.jit
            def fn(ssm, conv):
                # (layers, 1, slots, ...) -> one (layers, 1, ...) per slot
                return tuple((ssm[:, :, j], conv[:, :, j])
                             for j in range(ssm.shape[2]))

            self._snap_fns[key] = fn
        per = self.ecfg.prefix_bucket_blocks
        for j, snap in enumerate(self._snap_fns[key](ssm, conv)[:n]):
            i = first + (j + 1) * per - 1
            payloads[i] = tuple(payloads[i]) + snap
            units[i] += self.snapshot_units
        return units

    def _run_fresh(self, tokens: Sequence[int], keep: int = 0):
        S = _bucket(len(tokens), self.ecfg.suffix_buckets)
        # jit-key bucketing of the keep budget is owned by KVLifecycle
        keep_pad = self.kv.keep_pad(keep, S)
        key = (S, keep_pad)
        self._last_jit = ("fresh", key, key not in self._fresh_fns)
        self._last_shape = {"S": S}
        if key not in self._fresh_fns:
            self._step_compiled = True
            cfg = self.cfg
            if self.stateful:
                snap, n_snap = self.snap_every, self.kv.snapshot_slots(
                    keep_pad)

                @jax.jit
                def fn(params, toks, last_index):
                    return hybrid_model.pattern_prefill(
                        params, cfg, toks, kv_keep=keep_pad, snap_every=snap,
                        n_snap=n_snap, last_index=last_index)
            else:
                @jax.jit
                def fn(params, toks, last_index):
                    return tfm.prefill(params, cfg, {"tokens": toks},
                                       kv_keep=keep_pad, last_index=last_index)

            self._fresh_fns[key] = fn
        toks = np.zeros((1, S), np.int32)
        toks[0, :len(tokens)] = tokens
        self._skipped_tokens = skipped_slots(S, len(tokens),
                                             self.cfg.hybrid_chunk)
        logits, kv = self._fresh_fns[key](
            self.params, jnp.asarray(toks),
            jnp.asarray([len(tokens) - 1], jnp.int32))
        if not kv:
            return logits, {"k": None, "v": None}, 0
        # kv: (L, 1, keep_pad, KV, hd); valid fresh tokens = len(tokens),
        # usable budget = the caller's keep (keep_pad only pads the jit key)
        n_new = min(keep, keep_pad, len(tokens))
        return logits, kv, n_new

    def _execute_packed(self, batch: List[Request]) -> jax.Array:
        """Run N requests (cache hits AND misses) as one prepacked forward.

        Returns (N, V) logits — one row per request. Hit segments pack only
        their SUFFIX tokens; their cached prefix KV is gathered into one
        contiguous per-segment prefix buffer the packed attention reads
        through position-masked segment restriction
        (``tfm.prefill_packed_with_prefix``). All-miss batches take the
        plain ``tfm.prefill_packed`` path unchanged.

        Suffix discard is per-segment, which a packed-sequence prefix budget
        cannot express, so the forward gathers exactly each request's keep
        window via ``kv_indices``: the stacked KV costs K kept tokens (same
        bound as the solo path), not S, and each window is inserted under
        its own chain — hits extend their chain past the reused prefix, so
        cache inserts keep the solo-path memory bound.
        """
        bs = self.ecfg.block_size
        N = len(batch)
        # cache probe + pin under the lock; the forward runs outside it so
        # router/admission probes never block on compute (solo-path rule)
        prefs: List[Tuple[int, List, int]] = []
        with self._phase("cache_match"), self.lock:
            for r in batch:
                matched = self._match_restoring(r.chain, rid=r.req_id)
                plen = self._usable_prefix_len(r.n_input, matched)
                r.n_cached_at_start = plen
                payloads = []
                if plen:
                    self.cache.pin(r.chain, plen // bs)
                    payloads = self.cache.match_payloads(
                        r.chain)[:plen // bs]
                prefs.append((plen, payloads, matched))
                self._matched_tokens += matched * bs
                self._resumed_tokens += plen
                self.hit_tokens += plen
                self.total_tokens += r.n_input
        suffixes = [r.n_input - p for r, (p, _, _) in zip(batch, prefs)]
        total = sum(suffixes)
        # realized step shape — the SAME arithmetic batch formation priced
        # the pack with (_pack_shape): per-segment prefix pad on a coarse
        # ladder (the jit key space is a product of ladders and batch
        # composition shifts step to step, so pmax must quantize hard or
        # steady state keeps compiling); rows padded to a power of two;
        # sub-bucket smax floor (hit suffixes are typically a few tens of
        # tokens and the batched attention's dominant einsum scales with
        # smax — padding 34 real tokens to the 64-token forward bucket
        # would burn ~2x there)
        S, Nb, smax, pmax, _ = self._pack_shape(
            [(r.n_input - p, p) for r, (p, _, _) in zip(batch, prefs)])
        # block-aligned NEW keep per request (only whole blocks are
        # insertable; a hit's cached prefix already covers its first
        # blocks). A chain already resident past its keep bound needs NO
        # fresh KV at all — steady-state repeat traffic then skips both the
        # forward's kv gather and the insert-side slicing entirely.
        keeps = [self.kv.keep_new(r.n_input, p, matched)
                 for r, (p, _, matched) in zip(batch, prefs)]
        # pad the gather length to a bucket so jit keys stay bounded; on the
        # hit path tie it to S outright (sum(keeps) <= packed suffix tokens)
        if not sum(keeps):
            K = 0
        elif pmax:
            K = S
        else:
            K = _bucket(sum(keeps), self.ecfg.suffix_buckets)
        toks = np.zeros((1, S), np.int32)
        segs = np.full((1, S), -1, np.int32)   # -1 = padding slack
        pos = np.zeros((1, S), np.int32)
        # last_idx is padded to max_pack_requests so the jit cache keys only
        # on the bucket shape, not on the batch size (duplicate rows of the
        # last real segment's logits are computed and dropped — N x V is
        # noise next to the forward)
        last_idx = np.zeros((max(N, self.ecfg.max_pack_requests),), np.int32)
        kv_idx = np.zeros((K,), np.int32)
        seg_qidx = np.full((Nb, smax), -1, np.int32)
        inv_idx = np.zeros((S,), np.int32)
        # padding prefix slots get a huge position: the causal mask
        # (suffix pos >= prefix pos) kills them
        ppos = np.full((Nb, pmax), PAD_POS, np.int32)
        off = cum = 0
        for n, r in enumerate(batch):
            plen = prefs[n][0]
            L = suffixes[n]
            toks[0, off:off + L] = r.tokens[plen:]
            segs[0, off:off + L] = n
            # RoPE restarts at each segment's OWN prefix length
            pos[0, off:off + L] = plen + np.arange(L)
            last_idx[n] = off + L - 1
            kv_idx[cum:cum + keeps[n]] = off + np.arange(keeps[n])
            seg_qidx[n, :L] = off + np.arange(L)
            inv_idx[off:off + L] = n * smax + np.arange(L)
            if pmax:
                ppos[n, :plen] = np.arange(plen)
            off += L
            cum += keeps[n]
        last_idx[N:] = last_idx[N - 1]
        # paid forward slots: the flat packed sequence S plus, on the hit
        # path, the per-row padded area the batched attention actually
        # computes over — Nb*pmax prefix slots AND the row slack
        # Nb*smax − S (a skewed pack's dominant waste term)
        self.padded_slots += S + Nb * pmax + (
            max(0, Nb * smax - S) if pmax else 0)
        self._last_shape = {"S": S, "Nb": Nb if pmax else 0, "smax": smax,
                            "pmax": pmax, "K": K}
        if pmax:
            # look the program up first: a new shape marks the step as
            # compiling (watchdog-exempt) before the gather runs
            fn = self._packed_hit_fn((S, Nb, smax, pmax, K))
            with self._phase("kv_gather"):
                pk = self._gather_prefix(prefs, 0, Nb, pmax)
                pv = self._gather_prefix(prefs, 1, Nb, pmax)
            with self._phase("dispatch"):
                logits, kv = fn(
                    self.params, jnp.asarray(toks), jnp.asarray(pos),
                    jnp.asarray(last_idx), pk, pv, jnp.asarray(ppos),
                    jnp.asarray(seg_qidx), jnp.asarray(inv_idx),
                    jnp.asarray(kv_idx))
                logits = logits[:N]
        else:
            with self._phase("dispatch"):
                logits, kv = self._run_packed_miss(S, K, toks, segs, pos,
                                                   last_idx, kv_idx)
                logits = logits[:N]
        now = time.perf_counter()
        with self._phase("kv_insert"), self.lock:
            # the keep windows are whole blocks laid end to end from 0 in
            # kv_idx order, so one split cuts every segment's blocks
            fresh = (self._split_blocks(kv, 0, sum(keeps) // bs)
                     if kv is not None else [])
            for n, r in enumerate(batch):
                plen, _, _ = prefs[n]
                if plen:
                    self.cache.unpin(r.chain, plen // bs)
                mine, fresh = fresh[:keeps[n] // bs], fresh[keeps[n] // bs:]
                # keeps[n] == 0: nothing insertable (or already resident —
                # the probe's match walk refreshed its LRU standing)
                if kv is not None and keeps[n]:
                    payloads_all = (self.cache.match_payloads(
                        r.chain)[:plen // bs] if plen else [])
                    self.cache.insert(r.chain, plen + keeps[n], now=now,
                                      payloads=payloads_all + mine)
        return logits

    def _gather_prefix(self, prefs: List[Tuple[int, List, int]], part: int,
                       Nb: int, pmax: int) -> jax.Array:
        """The pinned per-block prefix payloads (``part`` 0 = K, 1 = V) of
        each packed segment as one batched (L, Nb, pmax, KV, hd) buffer: row
        n is segment n's prefix, zero-padded; rows past the segments and
        segments with no prefix are zeros."""
        zero_row = jnp.zeros((self.cfg.num_layers, 1, pmax,
                              self.cfg.num_kv_heads, self.cfg.head_dim),
                             jnp.dtype(self.cfg.dtype))
        out = []
        for plen, payloads, _ in prefs:
            if not payloads:
                out.append(zero_row)
                continue
            buf = jnp.concatenate([p[part] for p in payloads], axis=2)
            if plen < pmax:
                buf = jnp.pad(buf, ((0, 0), (0, 0), (0, pmax - plen),
                                    (0, 0), (0, 0)))
            out.append(buf)
        out += [zero_row] * (Nb - len(prefs))
        return jnp.concatenate(out, axis=1)

    def _split_blocks(self, kv: Dict, start: int,
                      n_blocks: int) -> List[Tuple[jax.Array, jax.Array]]:
        """Cut ``n_blocks`` cache-block payloads ``(k_b, v_b)`` out of a
        kept-KV output ``{"k", "v"}`` of shape (L, 1, T, KV, hd): block b is
        tokens ``start + b*bs`` to ``start + (b+1)*bs``.

        One jitted program cuts G = min(SPLIT_BLOCKS, T // bs) blocks at a
        traced start, so its key is the array's shape alone (not the
        per-request block count) and a cut takes ceil(n_blocks / G)
        dispatches. Each block is its own dynamic slice: a wanted block lies
        inside the array and never clamps; only the surplus blocks of the
        last call may run past the end, and they are dropped. The programs
        queue behind the forward; nothing here waits on the device."""
        if n_blocks <= 0:
            return []
        bs = self.ecfg.block_size
        shape, dtype = kv["k"].shape, kv["k"].dtype
        if start < 0 or start + n_blocks * bs > shape[2]:
            raise ValueError(f"blocks {start}+{n_blocks}x{bs} outside the "
                             f"{shape[2]}-token KV")
        G = min(SPLIT_BLOCKS, shape[2] // bs)
        key = (shape, dtype, G)
        if key not in self._split_fns:
            self._step_compiled = True

            @jax.jit
            def fn(k, v, lo):
                return tuple(jax.lax.dynamic_slice_in_dim(x, lo + j * bs, bs,
                                                          axis=2)
                             for x in (k, v) for j in range(G))

            self._split_fns[key] = fn
        fn = self._split_fns[key]
        out: List[Tuple[jax.Array, jax.Array]] = []
        for b in range(0, n_blocks, G):
            parts = fn(kv["k"], kv["v"], np.int32(start + b * bs))
            m = min(G, n_blocks - b)
            out += zip(parts[:m], parts[G:G + m])
        programs = -(-n_blocks // G)
        self.kv_insert_blocks += n_blocks
        self.kv_insert_programs += programs
        if self.metrics is not None:
            inst = self.instance_name
            self.metrics.counter(
                "kv_insert_blocks", inst,
                help="cache blocks cut from fresh KV").inc(n_blocks)
            self.metrics.counter(
                "kv_insert_programs", inst,
                help="split programs dispatched to cut them").inc(programs)
        return out

    def _run_packed_miss(self, S: int, K: int, toks, segs, pos, last_idx,
                         kv_idx):
        key = (S, K)
        self._last_jit = ("packed_miss", key, key not in self._packed_fns)
        if key not in self._packed_fns:
            self._step_compiled = True
            cfg = self.cfg

            @jax.jit
            def fn(params, toks, segs, pos, last_idx, kv_idx):
                return tfm.prefill_packed(
                    params, cfg, toks, segs, pos, last_idx,
                    kv_indices=kv_idx if K else None)

            self._packed_fns[key] = fn
        return self._packed_fns[key](
            self.params, jnp.asarray(toks), jnp.asarray(segs),
            jnp.asarray(pos), jnp.asarray(last_idx), jnp.asarray(kv_idx))

    def _packed_hit_fn(self, key: Tuple[int, int, int, int, int]):
        """The jitted packed prefix-hit forward for ``key`` = (S, Nb, smax,
        pmax, K): ``prefill_packed_with_prefix`` over the batched (L, Nb,
        pmax, KV, hd) prefix buffers of ``_gather_prefix``."""
        self._last_jit = ("packed_hit", key,
                          key not in self._packed_hit_fns)
        if key not in self._packed_hit_fns:
            self._step_compiled = True
            cfg = self.cfg
            K = key[-1]

            @jax.jit
            def fn(params, toks, pos, last_idx, pk, pv, ppos, seg_qidx,
                   inv_idx, kv_idx):
                return tfm.prefill_packed_with_prefix(
                    params, cfg, toks, pos, last_idx, {"k": pk, "v": pv},
                    ppos, seg_qidx, inv_idx,
                    kv_indices=kv_idx if K else None)

            self._packed_hit_fns[key] = fn
        return self._packed_hit_fns[key]

    def _run_suffix(self, tokens, pk, pv, prefix_len: int, keep: int,
                    state0: Optional[Dict] = None):
        S = _bucket(len(tokens), self.ecfg.suffix_buckets)
        P = pk.shape[2]
        keep_new = self.kv.suffix_keep_new(keep, prefix_len, S)
        # jit-key bucketing of the fresh-KV budget (see _run_fresh)
        keep_pad = self.kv.keep_pad(keep_new, S)
        key = (S, P, keep_pad)
        self._last_jit = ("suffix", key, key not in self._suffix_fns)
        self._last_shape = {"S": S, "pmax": P}
        if key not in self._suffix_fns:
            self._step_compiled = True
            cfg = self.cfg
            if self.stateful:
                snap, n_snap = self.snap_every, self.kv.snapshot_slots(
                    keep_pad)

                @jax.jit
                def fn(params, toks, pk, pv, last_index, state0):
                    return hybrid_model.pattern_prefill(
                        params, cfg, toks, kv_keep=keep_pad, snap_every=snap,
                        n_snap=n_snap, prefix_len=P,
                        prefix_kv={"k": pk, "v": pv}, state0=state0,
                        last_index=last_index)
            else:
                @jax.jit
                def fn(params, toks, pk, pv, last_index):
                    return tfm.prefill_with_prefix(
                        params, cfg, {"tokens": toks}, {"k": pk, "v": pv},
                        prefix_len=P, kv_keep=P + keep_pad,
                        last_index=last_index)

            self._suffix_fns[key] = fn
        toks = np.zeros((1, S), np.int32)
        toks[0, :len(tokens)] = tokens
        self._skipped_tokens = skipped_slots(S, len(tokens),
                                             self.cfg.hybrid_chunk)
        args = (self.params, jnp.asarray(toks), pk, pv,
                jnp.asarray([len(tokens) - 1], jnp.int32))
        logits, kv = self._suffix_fns[key](
            *args, *((state0,) if self.stateful else ()))
        n_new = min(keep_new, len(tokens))
        return logits, kv, n_new

    # ---- output --------------------------------------------------------------
    def _score(self, logits: jax.Array, r: Request) -> Dict:
        """Constrained single-token output: renormalize over allowed ids
        (paper §2.3 — P(Yes)/P(No) without fine-tuning)."""
        out = {"req_id": r.req_id, "latency": r.latency,
               "n_cached": r.n_cached_at_start, "n_input": r.n_input,
               "deadline": r.deadline}
        logits = np.asarray(logits[0], np.float64)
        # non-finite guard: NaN logits reach scoring silently (softmax of
        # NaN is NaN, argmax of NaN is garbage) — flag the result corrupt
        # instead of delivering it; the serving layer quarantines and
        # retries on a peer. Constrained scoring needs every allowed logit
        # finite (renormalization); unconstrained argmax tolerates -inf
        # ("never this token") but not NaN or an all-non-finite row.
        if r.allowed_tokens:
            bad = not bool(np.isfinite(logits[list(r.allowed_tokens)]).all())
        else:
            bad = bool(np.isnan(logits).any()
                       or not np.isfinite(logits).any())
        if bad:
            self.nonfinite_results += 1
            self.result_guard.observe(float("nan"))
            out["corrupt"] = "nonfinite_logits"
            out["token"] = -1
            if r.allowed_tokens:
                out["scores"] = {}
            return out
        self.result_guard.observe(0.0)
        if r.allowed_tokens:
            sub = logits[list(r.allowed_tokens)]
            sub = np.exp(sub - sub.max())
            sub /= sub.sum()
            out["scores"] = {int(t): float(p)
                             for t, p in zip(r.allowed_tokens, sub)}
            out["token"] = int(r.allowed_tokens[int(np.argmax(sub))])
        else:
            out["token"] = int(np.argmax(logits))
        return out

    def stats(self) -> Dict:
        return {
            "steps": self.steps,
            "hit_rate": self.hit_tokens / max(1, self.total_tokens),
            "packed_steps": self.packed_steps,
            "packed_requests": self.packed_requests,
            "packed_hit_requests": self.packed_hit_requests,
            "pack_skew_splits": self.pack_skew_splits,
            "kv_insert_blocks": self.kv_insert_blocks,
            "kv_insert_programs": self.kv_insert_programs,
            "nonfinite_results": self.nonfinite_results,
            # fraction of paid forward slots that were padding/cache slack
            "padding_waste": 1.0 - (self.total_tokens
                                    / max(1, self.padded_slots)),
            "cache": self.cache.stats(),
            # JCT-calibration summary: coefficients, residual p50/p95,
            # refit counts — readable without scraping Prometheus
            "jct": self.jct_monitor.summary(),
        }
