"""Continuous-batching fleet serving engine (DESIGN.md §13).

The compact zoo path (DESIGN.md §10) made one decode step ~6x cheaper at
the paper's ~99% column-sparsity regime — but a cohort batching loop only
realizes that under closed-loop traffic where all prompts arrive together
and finish together. Under real churn (ragged arrivals, ragged lengths)
cohort slots idle from the moment their row finishes until the whole
batch drains. This module keeps the ONE compiled decode step hot:

  * **per-slot state lives on device** — position, prompt buffer, prompt
    length, tokens-remaining budget, active mask, feed token, and the
    per-request sample key are (B,)-shaped leaves of a ``slots`` pytree
    that rides through the jitted step;
  * **sampling and next-feed selection run inside the step** — the host
    never sees logits; each step returns only four (B,) arrays (sampled
    token, emitted/finished/truncated flags) that the host drains with a
    one-step lag so bookkeeping overlaps device compute;
  * **admission is a masked merge at the top of the SAME step** — freed
    slots take queued prompts between steps through a ``(mask, prompt,
    plen, budget, key)`` argument, so admit/evict/refresh/recompact all
    reuse the one trace (``n_traces`` extends the PR-6 contract);
  * **the KV cache and slot state are donated** — steady-state decode
    performs no per-step HBM copy of the cache (asserted via the
    ``input_output_alias`` entries of the compiled step's HLO).

Rows are independent through the decode step (per-row positions, per-row
cache masks), so a request admitted into a freed slot mid-flight produces
exactly the tokens a solo run of its prompt would — the continuous==solo
regression in tests/test_fleet_engine.py. The one exception is
capacity-factor MoE routing, which couples rows through expert capacity;
dense-MLP archs (the zoo's serving configs) are exactly row-independent.

Scan-state (SSM/hybrid) cache leaves are recurrent rather than
position-indexed, so slot reuse zeroes the admitted rows of those leaves
inside the step; position-indexed KV leaves are self-cleaning (the
attention mask reads only positions the current request wrote).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.transformer import decode_step, init_cache
from .compact import CompactModel, compact_model, support_selection
from .refresh import refresh_model, recompact_model

__all__ = ["EngineConfig", "Request", "Completion", "LatencyStats",
           "RecompactScheduler", "FleetEngine"]

# cache leaves carrying recurrent (non-position-indexed) state: stale rows
# WOULD leak into a newly admitted request, so the step zeroes them under
# the admit mask. Position-indexed leaves (k/v/c/kr) are self-cleaning.
_RECURRENT_CACHE_KEYS = frozenset({"state", "conv_x", "conv_B", "conv_C"})


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static serving-engine configuration (one compiled step per config).

    ``max_seq``: KV-cache slot depth Smax — a request stops (and is flagged
    ``truncated``) when its next position would reach it. ``max_prompt``:
    on-device prompt buffer width (defaults to ``max_seq``); longer prompts
    are refused at submit. ``temperature``: 0 = greedy argmax inside the
    step; > 0 samples with a per-request key folded with the row position
    (so continuous and solo runs of the same request draw the same
    stream). ``cache_dtype``: KV-cache dtype — ``None`` matches the first
    floating param leaf (bf16 checkpoints decode through bf16 caches
    instead of the old hard-coded f32). ``pipeline``: drain step outputs
    with a one-step lag so host bookkeeping overlaps device compute.

    >>> cfg = EngineConfig(max_seq=256, temperature=0.0)
    """
    max_seq: int = 256
    max_prompt: Optional[int] = None
    temperature: float = 0.0     # 0 = greedy
    seed: int = 0
    cache_dtype: Any = None      # None -> match the checkpoint's param dtype
    pipeline: bool = True

    @property
    def prompt_width(self) -> int:
        """The (B, Pmax) on-device prompt buffer width (static)."""
        return self.max_seq if self.max_prompt is None else self.max_prompt


@dataclasses.dataclass
class Request:
    """One queued generation request (host-side bookkeeping).

    ``rid``: engine-assigned id; ``prompt``: token ids (1 <= len <=
    ``EngineConfig.prompt_width``); ``max_new``: generation budget;
    ``key``: (2,) uint32 per-request sample key; ``arrival``: wall-clock
    submit time (or the caller-provided open-loop arrival instant) that
    TTFT is measured from.

    >>> req = Request(rid=0, prompt=[1, 2], max_new=8,
    ...               key=np.zeros(2, np.uint32), arrival=0.0)
    """
    rid: int
    prompt: List[int]
    max_new: int
    key: np.ndarray
    arrival: float


@dataclasses.dataclass
class Completion:
    """One finished request: tokens plus per-request service telemetry.

    ``tokens`` is prompt + generated (the cohort ``generate`` convention);
    ``truncated`` is True when the row ran out of cache depth (``max_seq``)
    before emitting its full ``max_new`` budget — the silent-truncation
    fix: callers can now SEE that ``len(generated) < max_new`` was a
    capacity decision, not model behavior. ``ttft``: seconds from arrival
    to the first generated token; ``token_times``: drain timestamp per
    generated token (inter-token gaps feed the latency percentiles);
    ``evicted``: cancelled before finishing.

    >>> done = Completion(rid=0, tokens=[1, 2, 9], prompt_len=2,
    ...                   truncated=False, evicted=False, ttft=0.01,
    ...                   token_times=[0.01])
    """
    rid: int
    tokens: List[int]
    prompt_len: int
    truncated: bool
    evicted: bool
    ttft: Optional[float]
    token_times: List[float]

    @property
    def generated(self) -> List[int]:
        """The generated suffix (``tokens`` without the prompt)."""
        return self.tokens[self.prompt_len:]


@dataclasses.dataclass
class LatencyStats:
    """Percentile summary of a latency sample set (seconds).

    >>> LatencyStats.from_samples([0.1, 0.2, 0.3]).p50
    0.2
    """
    count: int
    mean: float
    p50: float
    p95: float
    p99: float

    @classmethod
    def from_samples(cls, samples: Sequence[float]) -> "LatencyStats":
        """Build from raw samples; empty input yields all-zero stats."""
        if not samples:
            return cls(count=0, mean=0.0, p50=0.0, p95=0.0, p99=0.0)
        a = np.asarray(samples, np.float64)
        return cls(count=int(a.size), mean=float(a.mean()),
                   p50=float(np.percentile(a, 50)),
                   p95=float(np.percentile(a, 95)),
                   p99=float(np.percentile(a, 99)))

    def as_dict(self) -> Dict[str, float]:
        """Plain-dict form for JSON benchmark artifacts."""
        return dataclasses.asdict(self)


@dataclasses.dataclass
class RecompactScheduler:
    """Hysteretic trigger for live re-compaction under checkpoint churn.

    Projected training only kills columns, so the live/slot ratio of a
    served ``CompactModel`` decays monotonically across refreshed
    checkpoints. Re-compacting (``recompact_model``) keeps the ``live``
    bookkeeping honest and re-packs the ascending prefix, but it costs a
    host-side re-gather — doing it on every refresh while the ratio
    hovers at a threshold would thrash. The rule: fire when the ratio
    first crosses below ``threshold``, then again only after it has
    dropped a further ``hysteresis`` since the LAST fire. A ratio
    oscillation narrower than ``hysteresis`` can never re-trigger.
    ``reslot_threshold``: below this ratio the padded slots dominate the
    GEMMs and a full (recompiling) ``compact_model`` re-slot pays off —
    surfaced as ``reslot_recommended``, never done implicitly.

    >>> sched = RecompactScheduler(threshold=0.9, hysteresis=0.05)
    """
    threshold: float = 0.9
    hysteresis: float = 0.05
    reslot_threshold: float = 0.5
    last_fired_ratio: float = 1.0 + 1e-9
    fires: int = 0

    def decide(self, ratio: float) -> bool:
        """True iff a recompact should run at this live/slot ratio."""
        if ratio >= self.threshold:
            return False
        if ratio > self.last_fired_ratio - self.hysteresis:
            return False
        self.last_fired_ratio = ratio
        self.fires += 1
        return True

    def reslot_recommended(self, ratio: float) -> bool:
        """True when the ratio is low enough that a recompiling re-slot
        (fresh ``compact_model`` + step swap) would pay for itself."""
        return ratio < self.reslot_threshold


def _request_key(seed: int, sample_seed: int) -> np.ndarray:
    """Host-side per-request PRNG key: splitmix64 of (engine seed,
    request seed) as a (2,) uint32 key. Pure python — a jax.random call
    here would dispatch a device computation per submit, which under
    open-loop load costs more than the decode steps themselves."""
    mask = (1 << 64) - 1
    x = ((seed & 0xFFFFFFFF) << 32) | (sample_seed & 0xFFFFFFFF)
    x = (x + 0x9E3779B97F4A7C15) & mask
    z = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & mask
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & mask
    z = z ^ (z >> 31)
    return np.array([z >> 32, z & 0xFFFFFFFF], np.uint32)


def _param_dtype(params) -> Any:
    """Dtype of the first floating leaf (sel leaves are int32 riders)."""
    for leaf in jax.tree_util.tree_leaves(params):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            return jnp.asarray(leaf).dtype
    return jnp.float32


def _cache_specs(cache, batch_axes):
    """Per-leaf PartitionSpecs sharding the batch dim of a decode cache:
    axis 1 for scan-stacked block caches (leading dim = cycles), axis 0
    for unstacked remainder blocks."""
    out = {}
    for key, sub in cache.items():
        spec = P(None, batch_axes) if key == "blocks" else P(batch_axes)
        out[key] = jax.tree_util.tree_map(lambda _: spec, sub)
    return out


def _batch0_specs(tree, batch_axes):
    """PartitionSpecs for pytrees whose every leaf has batch on axis 0
    (slot state, admit args, step outputs)."""
    return jax.tree_util.tree_map(
        lambda a: P(*((batch_axes,) + (None,) * (jnp.asarray(a).ndim - 1))),
        tree)


def _reset_recurrent(cache, mask):
    """Zero the admitted rows of recurrent cache leaves (SSM conv/state):
    unlike position-indexed KV leaves, their stale values WOULD leak into
    a new request. mask: (B,) bool, True = slot (re)admitted this step."""
    keep = ~mask

    def _sub(sub, batch_axis):
        def one(path, leaf):
            name = getattr(path[-1], "key", None)
            if name in _RECURRENT_CACHE_KEYS:
                shape = [1] * leaf.ndim
                shape[batch_axis] = keep.shape[0]
                return leaf * keep.astype(leaf.dtype).reshape(shape)
            return leaf
        return jax.tree_util.tree_map_with_path(one, sub)

    return {k: _sub(sub, 1 if k == "blocks" else 0)
            for k, sub in cache.items()}


class FleetEngine:
    """Continuous-batching decode engine over one compiled step.

    ``model``: a zoo ``Model``; ``batch_slots``: fixed decode width B;
    ``cfg``: ``EngineConfig``; ``mesh``/``rules`` (optional): shard_map
    the step over the mesh axes the sharding rules assign to "batch"
    (params replicated, cache + slot state batch-sharded; rows are
    independent, so the step body contains zero collectives).

    Lifecycle: ``load``/``load_compact`` a checkpoint, ``submit`` requests,
    call ``step`` per decode step (or ``drain`` to run the backlog dry).
    ``refresh``/``recompact`` hot-swap checkpoints mid-flight without
    retracing; a ``RecompactScheduler`` (``scheduler=``) turns refreshes
    into recompactions when the live/slot ratio decays past its
    threshold. ``n_traces`` counts jit traces of the step — admission,
    eviction, refresh and recompaction all reuse trace #1.

    >>> eng = FleetEngine(model, batch_slots=4, cfg=EngineConfig())
    """

    def __init__(self, model, batch_slots: int, cfg: EngineConfig,
                 mesh=None, rules=None,
                 scheduler: Optional[RecompactScheduler] = None):
        if model.cfg.encdec or model.cfg.n_img_tokens:
            raise ValueError(
                "FleetEngine serves decoder-only archs; enc-dec / vision "
                "memory caches need per-request prefill plumbing")
        self.model = model
        self.cfg = cfg
        self.B = batch_slots
        self.scheduler = scheduler
        self.params = None
        self.compact: Optional[CompactModel] = None
        self.n_traces = 0            # bumps at TRACE time only (jit)
        self._mesh = mesh
        self._rules = rules
        self._step_fn = None         # built lazily: cache specs need shapes
        self._cache = None
        self._slots = None
        # host-side bookkeeping
        self._next_rid = 0
        self._queue: collections.Deque[Request] = collections.deque()
        self._reqs: Dict[int, Request] = {}
        self._slot_rid: List[Optional[int]] = [None] * batch_slots
        self._gen: Dict[int, List[int]] = {}
        self._times: Dict[int, List[float]] = {}
        self._cancelled: set = set()
        self._evict_pending: List[int] = []
        self._pending: collections.Deque = collections.deque()
        self._completions: List[Completion] = []
        self._retired: List[Completion] = []
        self._steps = 0
        self._tokens_out = 0

    # ---------------------- checkpoint lifecycle -------------------------

    def load(self, params) -> None:
        """Serve a dense checkpoint (drops any compact state)."""
        self.params = params
        self.compact = None

    def load_compact(self, compact: Optional[CompactModel] = None, *,
                     params=None) -> None:
        """Serve a compacted checkpoint: a prebuilt ``serve.CompactModel``
        or a dense ``params`` tree compacted here under the model's own
        ``projection_specs``."""
        if compact is None:
            compact = compact_model(params, self.model.cfg.projection_specs)
        self.compact = compact
        self.params = compact.params

    def _live_ratio(self, new_params) -> float:
        """Prospective min live/slot ratio of a new checkpoint against the
        frozen slot widths (host-side; checkpoint-rate, not step-rate)."""
        sups = support_selection(new_params, self.compact.specs)
        ratios = [sups[p].n_selected / max(self.compact.slot_width(p), 1)
                  for p in self.compact.sels]
        return min(ratios) if ratios else 1.0

    def refresh(self, new_dense_params) -> bool:
        """Hot refresh: new checkpoint values through the frozen compact
        recipe (or a plain param swap when serving dense). Shapes are
        unchanged, so the compiled step never retraces — safe mid-flight.
        With a ``scheduler``, decaying live/slot ratios upgrade the
        refresh to a live re-compaction; returns True when that fired."""
        if self.compact is None:
            self.params = new_dense_params
            return False
        if self.scheduler is not None and \
                self.scheduler.decide(self._live_ratio(new_dense_params)):
            self.recompact(new_dense_params)
            return True
        self.compact = refresh_model(self.compact, new_dense_params)
        self.params = self.compact.params
        return False

    def recompact(self, new_dense_params) -> None:
        """Live re-compaction: adopt the new checkpoint's (monotonically
        smaller) support inside the frozen slot widths. No retrace; exact
        mid-flight (surviving columns keep their ascending order, so the
        re-gathered GEMMs sum the same nonzero terms — DESIGN.md §13)."""
        self.compact = recompact_model(self.compact, new_dense_params)
        self.params = self.compact.params

    def reslot_recommended(self) -> bool:
        """True when the scheduler judges the live/slot ratio low enough
        that a full (recompiling) ``compact_model`` re-slot pays off."""
        if self.scheduler is None or self.compact is None:
            return False
        live = [self.compact.live[p] / max(self.compact.slot_width(p), 1)
                for p in self.compact.sels] or [1.0]
        return self.scheduler.reslot_recommended(min(live))

    # ---------------------- request intake -------------------------------

    def submit(self, prompt: Sequence[int], max_new: int,
               arrival: Optional[float] = None,
               sample_seed: Optional[int] = None) -> int:
        """Queue one request; returns its rid. ``arrival`` backdates the
        TTFT clock for open-loop load generators; ``sample_seed`` pins the
        per-request sample key (defaults to the rid) so a temperature>0
        request reproduces across solo and batched runs."""
        if not 0 < len(prompt) <= self.cfg.prompt_width:
            raise ValueError(
                f"prompt length {len(prompt)} outside (0, "
                f"{self.cfg.prompt_width}] — raise EngineConfig.max_prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        rid = self._next_rid
        self._next_rid += 1
        key = _request_key(
            self.cfg.seed, sample_seed if sample_seed is not None else rid)
        req = Request(rid=rid, prompt=list(prompt), max_new=max_new,
                      key=key,
                      arrival=time.perf_counter() if arrival is None
                      else arrival)
        self._queue.append(req)
        self._reqs[rid] = req
        return rid

    def cancel(self, rid: int) -> bool:
        """Evict a queued or in-flight request (its slot frees next step);
        returns False when the rid is unknown or already finished."""
        for i, q in enumerate(self._queue):
            if q.rid == rid:
                del self._queue[i]
                self._finalize(rid, evicted=True)
                return True
        for slot, srid in enumerate(self._slot_rid):
            if srid == rid and rid not in self._cancelled:
                self._cancelled.add(rid)
                self._evict_pending.append(slot)
                return True
        return False

    # ---------------------- step construction ---------------------------

    def _init_slots(self):
        B, Pmax = self.B, self.cfg.prompt_width
        return {
            "feed": jnp.zeros((B,), jnp.int32),
            "pos": jnp.zeros((B,), jnp.int32),
            "plen": jnp.ones((B,), jnp.int32),
            "remaining": jnp.zeros((B,), jnp.int32),
            "active": jnp.zeros((B,), bool),
            "prompt": jnp.zeros((B, Pmax), jnp.int32),
            "key": jnp.zeros((B, 2), jnp.uint32),
        }

    def _traced_step(self, params, cache, slots, admit):
        """The ONE compiled step: evict + admit-merge -> decode at per-row
        positions -> in-step sampling -> next-feed/budget/truncation
        update. Returns ((B,)-shaped outputs, cache, slots)."""
        self.n_traces += 1           # python side effect: trace-time only
        mcfg = self.model.cfg
        Smax = self.cfg.max_seq
        Pmax = self.cfg.prompt_width
        m = admit["mask"]
        active = slots["active"] & ~admit["evict"]
        slots = {
            "feed": jnp.where(m, admit["prompt"][:, 0], slots["feed"]),
            "pos": jnp.where(m, 0, slots["pos"]),
            "plen": jnp.where(m, admit["plen"], slots["plen"]),
            "remaining": jnp.where(m, admit["budget"], slots["remaining"]),
            "active": active | m,
            "prompt": jnp.where(m[:, None], admit["prompt"],
                                slots["prompt"]),
            "key": jnp.where(m[:, None], admit["key"], slots["key"]),
        }
        cache = _reset_recurrent(cache, m)
        logits, cache = decode_step(params, cache, slots["feed"][:, None],
                                    slots["pos"], mcfg)
        lg = logits[:, -1, :]
        if self.cfg.temperature > 0:
            keys = jax.vmap(jax.random.fold_in)(slots["key"], slots["pos"])
            nxt = jax.vmap(jax.random.categorical)(
                keys, lg / self.cfg.temperature)
        else:
            nxt = jnp.argmax(lg, axis=-1)
        nxt = nxt.astype(jnp.int32)

        active = slots["active"]
        pos, plen = slots["pos"], slots["plen"]
        rem = slots["remaining"]
        emitted = active & (pos >= plen - 1) & (rem > 0)
        new_rem = jnp.where(emitted, rem - 1, rem)
        done = active & (new_rem <= 0)
        want_more = active & ~done
        trunc = want_more & (pos + 1 >= Smax)
        new_active = want_more & ~trunc
        in_prompt = (pos + 1) < plen
        nxt_prompt = jnp.take_along_axis(
            slots["prompt"],
            jnp.clip(pos + 1, 0, Pmax - 1)[:, None], axis=1)[:, 0]
        new_feed = jnp.where(new_active & in_prompt, nxt_prompt,
                             jnp.where(new_active, nxt, slots["feed"]))
        out = {"token": nxt, "emitted": emitted,
               "finished": done | trunc, "truncated": trunc}
        slots = {**slots,
                 "feed": new_feed,
                 "pos": jnp.where(new_active, pos + 1, pos),
                 "remaining": new_rem,
                 "active": new_active}
        return out, cache, slots

    def _build_step(self, cache, slots, admit):
        if self._mesh is None:
            return jax.jit(self._traced_step, donate_argnums=(1, 2))

        from jax import shard_map
        from ..dist.sharding import default_rules
        rules = dict(default_rules() if self._rules is None else self._rules)
        batch_axes = rules.get("batch")
        if batch_axes is None:
            raise ValueError(
                "FleetEngine: the sharding rules map 'batch' to None — "
                "every rank would redundantly serve the FULL batch; name a "
                "mesh axis for 'batch' (see dist.sharding.default_rules)")
        cspecs = _cache_specs(cache, batch_axes)
        sspecs = _batch0_specs(slots, batch_axes)
        aspecs = _batch0_specs(admit, batch_axes)
        ospecs = {k: P(batch_axes)
                  for k in ("token", "emitted", "finished", "truncated")}
        fn = shard_map(self._traced_step, mesh=self._mesh,
                       in_specs=(P(), cspecs, sspecs, aspecs),
                       out_specs=(ospecs, cspecs, sspecs),
                       check_vma=False)
        return jax.jit(fn, donate_argnums=(1, 2))

    def _ensure_ready(self):
        if self.params is None:
            raise RuntimeError("no checkpoint loaded: call load/load_compact")
        if self._cache is None:
            dtype = (self.cfg.cache_dtype
                     if self.cfg.cache_dtype is not None
                     else _param_dtype(self.params))
            self._cache = init_cache(self.model.cfg, self.B,
                                     self.cfg.max_seq, dtype)
            self._slots = self._init_slots()
        if self._step_fn is None:
            self._step_fn = self._build_step(
                self._cache, self._slots, self._admit_proto())

    def step_hlo(self) -> str:
        """Compiled-step HLO text (collective / donation-alias audits)."""
        self._ensure_ready()
        return self._step_fn.lower(
            self.params, self._cache, self._slots,
            self._admit_proto()).compile().as_text()

    # ---------------------- the serving loop -----------------------------

    def _admit_proto(self):
        """A no-op admission merge (the all-False masks every step reuses
        as its starting point; also the spec/lowering prototype)."""
        B, Pmax = self.B, self.cfg.prompt_width
        return {"mask": np.zeros((B,), bool),
                "evict": np.zeros((B,), bool),
                "prompt": np.zeros((B, Pmax), np.int32),
                "plen": np.ones((B,), np.int32),
                "budget": np.zeros((B,), np.int32),
                "key": np.zeros((B, 2), np.uint32)}

    def _admit_args(self):
        """Build this step's admission/eviction merge (host numpy)."""
        B = self.B
        proto = self._admit_proto()
        mask, evict = proto["mask"], proto["evict"]
        prompt, plen = proto["prompt"], proto["plen"]
        budget, key = proto["budget"], proto["key"]
        for slot in self._evict_pending:
            evict[slot] = True
            rid = self._slot_rid[slot]
            self._slot_rid[slot] = None
            if rid is not None:
                self._finalize(rid, evicted=True)
        self._evict_pending = []
        for i in range(B):
            if not self._queue:
                break
            if self._slot_rid[i] is None:
                req = self._queue.popleft()
                mask[i] = True
                prompt[i, : len(req.prompt)] = req.prompt
                plen[i] = len(req.prompt)
                budget[i] = req.max_new
                key[i] = req.key
                self._slot_rid[i] = req.rid
                self._gen[req.rid] = []
                self._times[req.rid] = []
        return {"mask": mask, "evict": evict, "prompt": prompt,
                "plen": plen, "budget": budget, "key": key}

    def _finalize(self, rid: int, truncated: bool = False,
                  evicted: bool = False):
        req = self._reqs.pop(rid)
        gen = self._gen.pop(rid, [])
        times = self._times.pop(rid, [])
        self._cancelled.discard(rid)
        done = Completion(
            rid=rid, tokens=list(req.prompt) + gen,
            prompt_len=len(req.prompt), truncated=truncated,
            evicted=evicted,
            ttft=(times[0] - req.arrival) if times else None,
            token_times=times)
        self._completions.append(done)
        self._retired.append(done)

    def _drain_one(self, pending) -> None:
        """Host-side drain of ONE step's (B,) outputs: append emitted
        tokens, retire finished rows, free their slots. ``pending`` pairs
        the outputs with the slot->rid map AT DISPATCH TIME — with the
        one-step drain lag a slot can be evicted and re-admitted before
        its old output drains, and the token must credit the old rid."""
        out, owners = pending
        now = time.perf_counter()
        token = np.asarray(out["token"])
        emitted = np.asarray(out["emitted"])
        finished = np.asarray(out["finished"])
        truncated = np.asarray(out["truncated"])
        for i in range(self.B):
            rid = owners[i]
            if rid is None or rid not in self._gen:
                continue             # empty slot, or evicted + finalized
            if emitted[i]:
                self._gen[rid].append(int(token[i]))
                self._times[rid].append(now)
                self._tokens_out += 1
            if finished[i]:
                if self._slot_rid[i] == rid:
                    self._slot_rid[i] = None
                self._finalize(rid, truncated=bool(truncated[i]))

    def step(self) -> List[Completion]:
        """One engine step: admit queued prompts into freed slots, run the
        compiled decode step, drain the previous step's outputs (one-step
        pipeline lag; ``pipeline=False`` drains synchronously). Returns
        the requests that finished at the drained step."""
        self._ensure_ready()
        admit = self._admit_args()
        out, self._cache, self._slots = self._step_fn(
            self.params, self._cache, self._slots, admit)
        self._pending.append((out, tuple(self._slot_rid)))
        self._steps += 1
        lag = 1 if self.cfg.pipeline else 0
        while len(self._pending) > lag:
            self._drain_one(self._pending.popleft())
        return self._pop_completions()

    def flush(self) -> List[Completion]:
        """Drain every undrained step output (no new device step)."""
        while self._pending:
            self._drain_one(self._pending.popleft())
        return self._pop_completions()

    def drain(self, max_steps: Optional[int] = None) -> List[Completion]:
        """Run steps until the queue and all slots are empty (or
        ``max_steps`` is hit); returns all completions, rid-ordered."""
        done: List[Completion] = []
        steps = 0
        while self._queue or any(r is not None for r in self._slot_rid):
            done += self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        done += self.flush()
        return sorted(done, key=lambda c: c.rid)

    def _pop_completions(self) -> List[Completion]:
        out, self._completions = self._completions, []
        return out

    # ---------------------- telemetry ------------------------------------

    def latency_report(self) -> Dict[str, Any]:
        """TTFT and inter-token latency percentiles over every finished
        request since construction (seconds)."""
        ttft = [c.ttft for c in self._done_log if c.ttft is not None]
        gaps: List[float] = []
        for c in self._done_log:
            ts = c.token_times
            gaps += [b - a for a, b in zip(ts, ts[1:])]
        return {"ttft": LatencyStats.from_samples(ttft).as_dict(),
                "per_token": LatencyStats.from_samples(gaps).as_dict()}

    @property
    def _done_log(self) -> List[Completion]:
        return self._retired

    def stats(self) -> Dict[str, Any]:
        """Engine counters: steps run, tokens emitted, slot occupancy,
        queue depth, traces, live compaction ratios."""
        busy = sum(r is not None for r in self._slot_rid)
        out: Dict[str, Any] = {
            "steps": self._steps, "tokens": self._tokens_out,
            "busy_slots": busy, "queue": len(self._queue),
            "n_traces": self.n_traces,
            "slot_utilization": (self._tokens_out / (self._steps * self.B)
                                 if self._steps else 0.0),
        }
        if self.compact is not None:
            out["live_ratio"] = {
                p: self.compact.live[p] / max(self.compact.slot_width(p), 1)
                for p in self.compact.sels}
            out["reslot_recommended"] = self.reslot_recommended()
        return out
