"""Serving engine: persistent-slot continuous batching with a fixed-shape
decode step, WFQ slot packing, and per-tenant admission control.

**Slot lifecycle**:

1. The engine preallocates ONE ``(layers, max_batch, kv_cache_len, ...)``
   KV cache whose batch rows are long-lived *slots*, plus per-slot
   position / token vectors (layers/kvcache.py slot helpers).
2. A granted request is prefilled alone (batch 1), right-padded to a
   power-of-two *prompt bucket* — right padding sits causally after every
   real token, so bucketing never perturbs logits, and the prefill
   compile cache stays bounded at O(log max_prompt) entries.
3. ``kv_slot_insert`` writes the prefilled cache into the free slot; the
   slot joins the batch at its own position.
4. One jitted decode step advances ALL slots each tick.  Its shapes are
   functions of the slot geometry only — ``(max_batch, 1)`` tokens,
   ``(max_batch,)`` positions, the fixed cache — so it compiles **once
   per engine** regardless of the request mix.
5. A slot that finishes (EOS or token budget) is refilled from the queue
   *mid-decode* — no convoy effect: co-residents keep decoding while the
   freed slot takes new work.

At temperature 0 a request's tokens do not depend on its prompt bucket
or its co-residents: right-padded buckets sit causally after the prompt,
and a slot's stale bytes are validity-masked.

**WFQ slot packing** is the QoS mechanism: a weighted-fair-queueing
scheduler (:class:`WFQScheduler`) keeps a virtual time per tenant, with
weights from :class:`~repro.core.policies.QoSPolicy` ``rates``.  Granting
a slot advances the tenant's virtual time by the request's decode-step
cost over its weight, and the tenant with the smallest virtual time wins
the next free slot — so decode-slot occupancy splits proportionally to
weights under saturation.  ``ServeConfig.max_slots_per_tenant`` adds a
hard per-tenant budget on concurrently held slots.  The host-side token
bucket (:class:`~repro.core.mediation.HostTokenBucket`) still gates
admission underneath WFQ, charging ``len(prompt)`` tokens per request
(the host analogue of the traced bucket's byte-proportional debits);
bucket-starved grants are counted as deferrals.  Occupancy, grants and
deferrals land in :meth:`Engine.tenant_report` and, in counter-block
layout, :meth:`Engine.runtime_counters`; attach a
:class:`~repro.core.obs.CounterTimeline` (``Engine(..., obs=...)``) to
stream that block — plus active-slot / queue-depth gauges — into a
per-tick timeline artifact and sparkline panels (docs/observability.md).

**Paged KV cache** (``ServeConfig.block_size > 0``, docs/serving.md):
instead of one ``kv_cache_len`` stripe per slot, the engine owns ONE
shared pool of fixed-size blocks plus a per-slot host block table
(layers/kvcache.py ``kv_pool_*`` helpers).  Each decode tick runs the
model's paged decode step (``Model.decode_step_paged``): every layer
attends each slot's live blocks in place through its table
(kernels/paged_attention) plus the slot's new token, and the new tokens
are written into the donated pool after the layer scan — no op holds the
``max_batch × tables_len × block_size`` positions of the whole tables.
At temperature 0 it emits the stripe layout's tokens, with logits equal
within float tolerance (tests/test_serve_paged.py).  Prefill
allocates a request's cover blocks at grant; decode growth claims one
block at a time, and pool pressure (or a lowered slot budget,
:meth:`Engine.set_slot_budget`) *preempts* a running slot: its emitted
tokens are the snapshot (writes are idempotent), its blocks return to
the pool, and the request re-queues for recompute/resume — counted as
``preemptions``/``restores`` in the tenant counter block and surfaced as
``preempt_s``/``restore_s`` timeline rates.  Slot count thus decouples
from context length: a prompt longer than any fixed stripe is admissible
while free blocks exist.

**Chunked prefill** (``ServeConfig.prefill_chunk > 0``): a prompt longer
than one chunk is prefilled one ``(1, prefill_chunk)`` chunk per engine
tick at a traced offset (``Model.prefill_chunk``), interleaved with the
decode ticks of co-resident slots — a long prompt no longer monopolizes
the engine, bounding co-residents' p99 TTFT — and every chunk pays its
mediation cost through the same fused pipeline as a decode tick.

**Spans** (docs/observability.md): every continuous-path tick is a
``serve/tick`` profiler span holding its phases — ``serve/schedule``,
``serve/prefill`` (one whole prefill or one chunk), ``serve/blocks``,
``serve/decode``, ``serve/sample``, ``serve/emit``, ``serve/observe`` —
and every program is a named function (``serve_decode``,
``serve_prefill``, ``serve_prefill_chunk``, ...), so a
``jax.profiler`` trace of ``Engine.run`` ties each device module to its
phase on one clock.  Outside a profiler session a span costs about a
microsecond.
"""

from __future__ import annotations

import time
from collections import Counter, defaultdict, deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig, ServeConfig
from repro.core import telemetry as tl
from repro.core.mediation import HostTokenBucket
from repro.core.policies import QoSPolicy
from repro.layers.kvcache import (
    BlockAllocator,
    kv_cache_constrain,
    kv_pool_init,
    kv_pool_insert,
    kv_pool_scatter_chunk,
    kv_pool_scatter_token,
    kv_slot_insert,
    slot_vectors_init,
    state_slot_insert,
)

# Bound on consecutive all-throttled refill rounds before the engine
# force-admits the queue head (guarantees progress under any rate config).
_MAX_STARVED_ROUNDS = 10_000
_MIN_PROMPT_BUCKET = 8


class ServeError(ValueError):
    """A request the engine cannot serve under the current ServeConfig —
    raised at *submit* time (capacity checks), never mid-decode."""


@dataclass(eq=False)                 # identity semantics: rid is
class Request:                       # caller-supplied and prompt is an
    rid: int                         # ndarray (elementwise ==)
    prompt: np.ndarray               # (prompt_len,) int32
    max_new_tokens: int = 16
    tenant: str = "default"
    out_tokens: list = field(default_factory=list)
    done: bool = False
    t_first: float | None = None     # perf_counter stamp of the first token
    # a list here makes the engine append, per emitted token, the float32
    # logits row it was sampled from (the serve-vs-reference check)
    logits: list | None = None


def sample(logits: jax.Array, rng, temperature: float):
    if temperature <= 0:
        return logits.argmax(-1).astype(jnp.int32)
    return jax.random.categorical(rng, logits / temperature).astype(jnp.int32)


def prompt_bucket(n: int) -> int:
    """Power-of-two prompt capacity ≥ max(n, 8): bounds the number of
    distinct prefill shapes (and thus compiles) at O(log max_prompt)."""
    b = _MIN_PROMPT_BUCKET
    while b < n:
        b *= 2
    return b


class WFQScheduler:
    """Weighted fair queueing over decode slots.

    Each tenant carries a *virtual time*; granting a slot advances it by
    the request's expected decode-step cost divided by the tenant's
    weight.  The backlogged tenant with the smallest virtual time wins
    the next free slot, so long-run slot grants — and decode-slot
    occupancy — split proportionally to weights under saturation.

    A monotone *virtual clock* tracks the smallest virtual time among
    the tenants backlogged each scheduling round (``note_backlog``); a
    grant starts no earlier than the clock, so a tenant re-entering
    after idling resumes at the current service level instead of
    spending its idle time as hoarded credit.  Unknown tenants get
    ``default_weight``."""

    def __init__(self, weights: dict[str, float] | None = None,
                 default_weight: float = 1.0):
        self.weights = dict(weights or {})
        self.default_weight = float(default_weight)
        self.vtime: dict[str, float] = {}
        self.vclock = 0.0

    def weight(self, tenant: str) -> float:
        return max(float(self.weights.get(tenant, self.default_weight)),
                   1e-9)

    def order(self, tenants) -> list[str]:
        """Tenants in grant-preference order (smallest virtual time
        first; ties keep the caller's order)."""
        return sorted(tenants, key=lambda t: self.vtime.get(t, 0.0))

    def note_backlog(self, tenants) -> None:
        """Advance the virtual clock to the backlogged minimum (call once
        per scheduling round with every queued or slot-holding tenant)."""
        vs = [self.vtime.get(t, 0.0) for t in tenants]
        if vs:
            self.vclock = max(self.vclock, min(vs))

    def grant(self, tenant: str, cost: float) -> None:
        v = max(self.vtime.get(tenant, 0.0), self.vclock)
        self.vtime[tenant] = v + float(cost) / self.weight(tenant)


class Engine:
    def __init__(self, model, params, cfg: ModelConfig, serve: ServeConfig,
                 dp=None, eos_id: int = 1, obs=None, obs_every: int = 1):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.scfg = serve
        self.dp = dp
        self.eos_id = eos_id
        # optional CounterTimeline (core/obs.py): one snapshot of the
        # per-tenant counter block + run gauges every ``obs_every``-th
        # decode tick (ObsConfig.every), taken on the host between jitted
        # steps — never inside traced code
        self.obs = obs
        self.obs_every = max(int(obs_every), 1)
        self._obs_tick_no = 0
        # control-plane hook: called as ``on_tick(engine)`` once per
        # decode tick, after the timeline snapshot that is due (if a
        # timeline is attached), so a ServeElasticController
        # (runtime/elastic.py) can observe the fresh window and move the
        # slot budget while the engine is mid-run
        self.on_tick = None
        # cache sharding edges are issued inside the traced prefill, so
        # policy enforcement/telemetry happen once per compiled shape (like
        # every other dataplane edge), not once per host batching round.
        # Every program is a named function: XLA names its module
        # ``jit_<name>`` on the device and ``PjitFunction(<name>)`` on the
        # host, which is how a profiler trace ties device time to the
        # engine's phases (docs/observability.md "Spans").
        # Prefill-to-slot is ONE traced op: batch-1 bucketed prefill whose
        # cache lands directly in the target slot of the persistent cache
        # (one dispatch per admitted request, one compile per prompt
        # bucket).
        def serve_prefill(p, t, pc, cache, slot, last):
            logits, pc = model.prefill(p, {"tokens": t},
                                       kv_cache_constrain(dp, pc),
                                       dp=dp, last_pos=last)
            # family-agnostic: writes KV stripe leaves AND recurrent /
            # cross-attention state leaves at their batch row
            return logits, state_slot_insert(cache, pc, slot)

        def serve_decode(p, t, c, pos):
            return model.decode_step_slots(p, t, c, pos, dp=dp)

        # the persistent cache is donated: XLA updates it in place instead
        # of copying the full buffer per tick / per insert (a no-op with a
        # warning on backends without aliasing)
        self._prefill_slot = jax.jit(serve_prefill, donate_argnums=(3,))
        self._step_slots = jax.jit(serve_decode, donate_argnums=(2,))

        # True for the recurrent families (mamba/xLSTM state): the engine
        # prefills them at exact prompt length — right padding advances a
        # recurrence, so bucketed prefill would corrupt the slot state
        # (one prefill compile per distinct prompt length, correctness
        # over compile reuse)
        self._recurrent = bool(getattr(model, "recurrent", False))

        # ---- paged KV block pool (block_size > 0) ---------------------
        bs = serve.block_size
        self.paged = bs > 0
        step_paged = getattr(model, "decode_step_paged", None)
        if self.paged:
            spec = jax.eval_shape(lambda: model.init_cache(1, bs))
            pageable = (step_paged is not None and isinstance(spec, dict)
                        and set(spec) == {"k", "v"}
                        and all(len(v.shape) == 5 for v in spec.values()))
            if not pageable:
                # name the family and the flag — never a capacity message:
                # the config is *valid*, just not for this cache layout
                raise ServeError(
                    f"paged KV (block_size={bs}) is not supported for the "
                    f"{cfg.family!r} family ({cfg.name}): its decode cache "
                    f"holds recurrent/cross-attention state that cannot be "
                    f"block-paged. Set ServeConfig.block_size=0 "
                    f"(--block-size 0) to serve this family on the fixed "
                    f"stripe layout (continuous batching, chunk-exact "
                    f"preemption and WFQ budgets all still apply).")
            ks = spec["k"]
            # (layers, kv_heads, head_dim, dtype) from the model's own
            # cache layout, so the pool matches it bit-for-bit
            self._pool_geom = (ks.shape[0], ks.shape[3], ks.shape[4],
                               ks.dtype)
            self._n_usable = serve.n_blocks or \
                (serve.max_batch * serve.kv_cache_len // bs)
            self._tables_len = self._n_usable

            def serve_decode(p, t, pool, tables, pos, act):
                # each slot's blocks are read in place; an inactive slot
                # reads none (position 0), its logits row is never read
                # and its token write puts back what is there.  ``rows``:
                # an expert share's rows per MoE layer and held expert
                # (None without one)
                logits, new, rows = step_paged(p, t, pool, tables,
                                               jnp.where(act, pos, 0), dp=dp)
                return logits, kv_pool_scatter_token(pool, new, tables,
                                                     pos, act, bs), rows

            def serve_pool_insert(pool, pc, ids):
                return kv_pool_insert(pool, pc, ids, bs)

            def serve_prefill(p, t, c, last):
                return model.prefill(p, {"tokens": t},
                                     kv_cache_constrain(dp, c), dp=dp,
                                     last_pos=last)

            self._step_pool = jax.jit(serve_decode, donate_argnums=(2,))
            self._pool_insert = jax.jit(serve_pool_insert,
                                        donate_argnums=(0,))
            self._prefill_last = jax.jit(serve_prefill)

        # ---- chunked prefill (prefill_chunk > 0) ----------------------
        chunk_fn = getattr(model, "prefill_chunk", None)
        self.chunked = serve.prefill_chunk > 0 and chunk_fn is not None
        if self.chunked:
            def serve_prefill_chunk(p, t, c, off, last):
                return chunk_fn(p, {"tokens": t}, kv_cache_constrain(dp, c),
                                off, dp=dp, last_pos=last)

            def serve_chunk_scatter(pool, pc, trow, off):
                return kv_pool_scatter_chunk(pool, pc, trow, off,
                                             serve.prefill_chunk, bs)

            def serve_slot_insert(c, pc, s):
                return state_slot_insert(c, pc, s)

            self._chunk = jax.jit(serve_prefill_chunk, donate_argnums=(2,))
            if self.paged:
                self._chunk_scatter = jax.jit(serve_chunk_scatter,
                                              donate_argnums=(0,))
            else:
                self._slot_ins = jax.jit(serve_slot_insert,
                                         donate_argnums=(0,))

        # per-run slot bookkeeping (reset by _run_continuous)
        self._prefills: dict[int, dict] = {}
        self._prefill_q: deque = deque()
        self._budget_cap = 0             # 0 = use scfg.max_slots_per_tenant
        qos = next((p for p in (dp.policies if dp is not None else [])
                    if isinstance(p, QoSPolicy)), None)
        self._buckets = HostTokenBucket.from_policy(
            qos, scale=serve.admission_token_scale)
        self._wfq = WFQScheduler(qos.rates if qos is not None else {})
        self.tenant_stats: dict[str, dict[str, float]] = defaultdict(
            lambda: {"requests": 0, "tokens": 0, "deferrals": 0,
                     "wfq_grants": 0, "occupancy_steps": 0,
                     "preemptions": 0, "restores": 0})
        # running totals over the paged decode ticks of a model whose MoE
        # layers run through an expert share: rows its held experts
        # computed, and (layer, held expert) pairs that had any
        self.moe_stats = {"ticks": 0, "moe_rows": 0, "moe_experts_hit": 0}
        self._tenant_ids: dict[str, int] = {}

    def _tenant_id(self, tenant: str) -> int:
        """Stable small integer per tenant (for the slot tenant vector)."""
        return self._tenant_ids.setdefault(tenant, len(self._tenant_ids))

    # ------------------------------------------------------------------
    # tenant admission (host-side token bucket, serve-level throttling)
    # ------------------------------------------------------------------
    @staticmethod
    def _admission_cost(r: Request, bucket: HostTokenBucket | None) -> float:
        """Bucket debit for admitting ``r``: its prompt tokens, clamped to
        the bucket's burst so a prompt longer than the bucket can ever
        hold still drains a full bucket instead of being permanently
        inadmissible (the classic token-bucket cost clamp)."""
        cost = float(len(r.prompt))
        return min(cost, bucket.burst) if bucket is not None else cost

    def _obs_snapshot(self, *, active: int, queued: int) -> None:
        """The end of one engine tick, seen from outside: the attached
        timeline gets the snapshot that is due (the serve counter block —
        WFQ grants / tokens / occupancy / deferrals in telemetry column
        layout — plus slot-level run gauges), then ``on_tick`` fires,
        every tick, with or without a timeline."""
        if self.obs is not None:
            self._obs_tick_no += 1
            if self._obs_tick_no % self.obs_every == 0:
                ctrs, tenants = self.runtime_counters()
                gauges = {"active_slots": active, "queued": queued}
                if self.paged and getattr(self, "_alloc", None) is not None:
                    gauges["free_blocks"] = self._alloc.free_blocks
                self.obs.snapshot_block(self._obs_tick_no, ctrs, tenants,
                                        gauges=gauges)
        if self.on_tick is not None:
            self.on_tick(self)

    # ------------------------------------------------------------------
    def _finish(self, r: Request, done: list[Request]) -> None:
        r.done = True
        stats = self.tenant_stats[r.tenant]
        stats["requests"] += 1
        stats["tokens"] += len(r.out_tokens)
        done.append(r)

    def _emit(self, r: Request, token: int, logits, row: int) -> None:
        """Append ``token`` to ``r``; ``logits[row, -1]`` is the row it
        was sampled from, copied to the host only when ``r`` asks."""
        if not r.out_tokens:
            r.t_first = time.perf_counter()
        r.out_tokens.append(token)
        if r.logits is not None:
            r.logits.append(np.asarray(logits[row, -1], np.float32))

    # ------------------------------------------------------------------
    # public entry
    # ------------------------------------------------------------------
    def run(self, requests: list[Request], rng=None) -> list[Request]:
        """Serve all requests to completion; returns them with outputs."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return self._run_continuous(list(requests), rng)

    # ------------------------------------------------------------------
    # continuous: persistent slots, fixed-shape decode, WFQ packing
    # ------------------------------------------------------------------
    def _cover(self, n: int) -> int:
        """Prefill cache capacity for an ``n``-token sequence: the chunk
        cover (smallest multiple of ``prefill_chunk`` ≥ n) when chunked
        prefill applies, else the power-of-two prompt bucket.

        Recurrent families get the EXACT length: their prefill runs every
        cache position through the mamba/xLSTM recurrence, so padding to a
        bucket would fold pad tokens into the slot state.  Costs one
        prefill compile per distinct prompt length — the documented
        correctness-first tradeoff (docs/serving.md)."""
        if self._recurrent:
            return max(n, 1)
        C = self.scfg.prefill_chunk
        if self.chunked and n > C:
            return -(-n // C) * C
        return prompt_bucket(n)

    @staticmethod
    def _resume_len(r: Request) -> int:
        """Tokens re-prefilled when ``r`` restarts: the prompt plus every
        emitted token but the last (which becomes the pending decode
        input) — 0 emitted means a fresh start over the prompt alone."""
        k = len(r.out_tokens)
        return len(r.prompt) + k - 1 if k else len(r.prompt)

    def _blocks_for(self, r: Request) -> int:
        return -(-self._cover(self._resume_len(r)) // self.scfg.block_size)

    def _bucket_cap(self, prompt_len: int) -> int:
        cap = self._cover(prompt_len)
        need = cap + self.scfg.max_new_tokens + 1
        if need > self.scfg.kv_cache_len:
            raise ServeError(
                f"request needs {need} cache positions (prefill cover {cap}"
                f" + max_new_tokens {self.scfg.max_new_tokens} + 1) but "
                f"kv_cache_len is {self.scfg.kv_cache_len}")
        return cap

    def _check_capacity(self, r: Request) -> None:
        """Submit-time admission check (raises :class:`ServeError`).

        Paged: worst-case pool blocks over the request's whole lifetime —
        the prefill cover, the resume cover after a worst-case preemption
        (every budgeted token emitted), and the decode high-water mark —
        must fit the pool.  Stripe: the legacy per-slot stripe check."""
        if not self.paged:
            self._bucket_cap(len(r.prompt))
            return
        L = len(r.prompt)
        limit = min(r.max_new_tokens, self.scfg.max_new_tokens)
        need = max(self._cover(L), self._cover(L + max(limit - 1, 0)),
                   L + limit) + 1
        nblk = -(-need // self.scfg.block_size)
        if nblk > self._n_usable:
            raise ServeError(
                f"request needs {nblk} pool blocks ({need} cache positions"
                f" / block_size {self.scfg.block_size}) but the pool has "
                f"only {self._n_usable} usable blocks")

    def _resume_fits(self, r: Request) -> bool:
        """Whether preempting ``r`` now leaves it restartable.  Always true
        under paging (the submit check covered the worst-case resume);
        stripe resume re-prefills a *longer* sequence whose cover can
        outgrow the slot stripe mid-bucket."""
        if self.paged:
            return True
        eff = self._resume_len(r)
        limit = min(r.max_new_tokens, self.scfg.max_new_tokens)
        return max(self._cover(eff),
                   len(r.prompt) + limit) + 1 <= self.scfg.kv_cache_len

    # ------------------------------------------------------------------
    # preemption (pool pressure / slot budgets) and resume
    # ------------------------------------------------------------------
    def set_slot_budget(self, n: int) -> int:
        """Tighten (or with 0, relax back to ServeConfig) the per-tenant
        cap on concurrently held slots — the serve-side elastic control
        knob.  Takes effect on the next engine tick: over-budget tenants
        have their most recent slots preempted.  Returns the previous raw
        override (0 = none) so an elastic controller can restore exactly
        the pre-shrink setting on grow-back."""
        prev, self._budget_cap = self._budget_cap, max(int(n), 0)
        return prev

    def slot_budget(self) -> int:
        """The *effective* per-tenant slot cap right now: the runtime
        override if set, else ``ServeConfig.max_slots_per_tenant``, else
        ``max_batch`` (no per-tenant cap ⇒ the batch is the ceiling)."""
        return int(self._budget_cap or self.scfg.max_slots_per_tenant
                   or self.scfg.max_batch)

    def _release_slot(self, slot: int, vecs) -> None:
        """Return a slot's resources (pool blocks, slot vectors)."""
        if self.paged and self._slot_blocks[slot]:
            self._alloc.free(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            self._tables[slot, :] = 0
        vecs["active"][slot] = False
        vecs["tenant"][slot] = -1

    def _preempt_slot(self, slot: int, slots, vecs, tok, ntok,
                      queue) -> None:
        """Evict the resident request: its emitted tokens ARE the snapshot
        (prefill/decode writes are idempotent, so recompute is exact at
        temperature 0), its blocks return to the pool, and it re-queues at
        the front for resume."""
        r = slots[slot]
        st = self._prefills.pop(slot, None)
        if st is not None:               # mid-chunk-prefill: drop partials
            try:
                self._prefill_q.remove(slot)
            except ValueError:
                pass
        slots[slot] = None
        self._release_slot(slot, vecs)
        vecs["pos"][slot] = 0
        ntok[slot] = 0
        self.tenant_stats[r.tenant]["preemptions"] += 1
        queue.appendleft(r)

    def _enforce_budget(self, slots, vecs, tok, ntok, queue) -> None:
        """Preempt over-budget tenants' most recent slots down to the
        effective per-tenant cap (``set_slot_budget`` overrides the
        ServeConfig value) — what makes WFQ budgets *enforceable* instead
        of advisory."""
        cap = self._budget_cap or self.scfg.max_slots_per_tenant
        if not cap:
            return
        held: dict[str, list[int]] = defaultdict(list)
        for i, r in enumerate(slots):
            if r is not None:
                held[r.tenant].append(i)
        for tenant, idxs in held.items():
            extra = len(idxs) - cap
            if extra <= 0:
                continue
            for i in sorted(idxs, key=lambda j: self._slot_started[j],
                            reverse=True):
                if extra <= 0:
                    break
                if not self._resume_fits(slots[i]):
                    continue             # stripe: resume would not fit
                self._preempt_slot(i, slots, vecs, tok, ntok, queue)
                extra -= 1

    def _ensure_blocks(self, i: int, slots, vecs, tok, ntok, queue) -> bool:
        """Guarantee slot ``i`` owns the block its next decode write lands
        in, claiming from the pool on demand.  Pool pressure preempts the
        active slot whose tenant has the largest WFQ virtual time (the
        least entitled co-resident); with no other candidate the slot
        preempts itself — deadlock-free, since the submit check bounds any
        single request's need to the pool size.  Returns False when slot
        ``i`` itself was preempted."""
        bs = self.scfg.block_size
        while vecs["active"][i] and \
                int(vecs["pos"][i]) // bs >= len(self._slot_blocks[i]):
            got = self._alloc.alloc(1)
            if got is not None:
                self._slot_blocks[i].append(got[0])
                self._tables[i, len(self._slot_blocks[i]) - 1] = got[0]
                continue
            cands = [j for j in range(self.scfg.max_batch)
                     if j != i and slots[j] is not None and vecs["active"][j]]
            if not cands:
                self._preempt_slot(i, slots, vecs, tok, ntok, queue)
                return False
            victim = max(cands, key=lambda j: (
                self._wfq.vtime.get(slots[j].tenant, 0.0),
                self._slot_started[j]))
            self._preempt_slot(victim, slots, vecs, tok, ntok, queue)
        return bool(vecs["active"][i])

    # ------------------------------------------------------------------
    # prefill-to-slot (whole or chunked; fresh or resume)
    # ------------------------------------------------------------------
    def _activate(self, r: Request, slot: int, logits, cache, slots, vecs,
                  tok, ntok, done, rng, *, eff: int, k: int):
        """Post-prefill slot activation.  Fresh requests (k=0) sample and
        emit their first token; resumed requests re-enter decode with the
        token that was pending when they were preempted (no new sample —
        recompute is exact)."""
        limit = min(r.max_new_tokens, self.scfg.max_new_tokens)
        if k == 0:
            rng, key = jax.random.split(rng)
            t = int(np.asarray(sample(logits[:, -1, :], key,
                                      self.scfg.temperature))[0])
            self._emit(r, t, logits, 0)
            if t == self.eos_id or limit <= 1:
                self._finish(r, done)                # slot stays free
                slots[slot] = None
                self._release_slot(slot, vecs)
                return cache, rng
            nt = 1
        else:
            self.tenant_stats[r.tenant]["restores"] += 1
            t = int(r.out_tokens[-1])
            nt = k
        slots[slot] = r
        vecs["pos"][slot] = eff
        vecs["active"][slot] = True
        vecs["tenant"][slot] = self._tenant_id(r.tenant)
        tok[slot, 0] = t
        ntok[slot] = nt
        self._slot_seq += 1
        self._slot_started[slot] = self._slot_seq
        return cache, rng

    def _start_request(self, r: Request, slot: int, cache, slots, vecs, tok,
                       ntok, done, rng):
        """Prefill one request (batch 1) into ``slot`` — whole when it fits
        one chunk/bucket, else enqueued for chunk-at-a-time prefill — and
        emit / restore its next decode token.  Returns (cache, rng); with
        paging, ``cache`` is the block pool."""
        scfg = self.scfg
        k = len(r.out_tokens)            # > 0 ⇒ resume after preemption
        eff = self._resume_len(r)
        seq = (np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.out_tokens[:-1], np.int32)])
               if k else np.asarray(r.prompt, np.int32))
        cover = self._cover(eff)
        if self.paged:
            ids = self._alloc.alloc(-(-cover // scfg.block_size))
            if ids is None:              # callers check free_blocks first
                raise RuntimeError("block pool exhausted at grant")
            self._slot_blocks[slot] = list(ids)
            self._tables[slot, :] = 0
            self._tables[slot, :len(ids)] = ids
        toks = np.zeros((1, cover), np.int32)
        toks[0, :eff] = seq              # right-pad
        if self.chunked and eff > scfg.prefill_chunk:
            # chunk-at-a-time: one chunk advances per engine tick,
            # interleaved with decode (run loop); slot is held but not
            # active until the last chunk lands
            self._prefills[slot] = {
                "r": r, "toks": toks, "eff": eff, "off": 0, "cover": cover,
                "pcache": self.model.init_cache(1, cover), "k": k}
            self._prefill_q.append(slot)
            slots[slot] = r
            vecs["tenant"][slot] = self._tenant_id(r.tenant)
            self._slot_seq += 1
            self._slot_started[slot] = self._slot_seq
            return cache, rng
        last = np.asarray([eff - 1], np.int32)
        with TraceAnnotation("serve/prefill", rid=r.rid, slot=slot, offset=0,
                             tokens=eff):
            pcache = self.model.init_cache(1, cover)
            if self.paged:
                logits, pcache = self._prefill_last(self.params,
                                                    jnp.asarray(toks), pcache,
                                                    jnp.asarray(last))
                cache = self._pool_insert(cache, pcache,
                                          jnp.asarray(ids, jnp.int32))
            else:
                logits, cache = self._prefill_slot(self.params,
                                                   jnp.asarray(toks), pcache,
                                                   cache, jnp.int32(slot),
                                                   jnp.asarray(last))
            return self._activate(r, slot, logits, cache, slots, vecs, tok,
                                  ntok, done, rng, eff=eff, k=k)

    def _advance_chunk(self, cache, slots, vecs, tok, ntok, done, rng):
        """Advance the oldest chunk-prefilling slot by ONE chunk (paying
        one mediation-accounted traced step), activating it when the last
        chunk lands.  Returns (cache, rng)."""
        slot = self._prefill_q.popleft()
        st = self._prefills[slot]
        C = self.scfg.prefill_chunk
        off = st["off"]
        with TraceAnnotation("serve/prefill", rid=st["r"].rid, slot=slot,
                             offset=off, tokens=min(C, st["eff"] - off)):
            chunk = st["toks"][:, off:off + C]
            last = np.asarray([st["eff"] - 1], np.int32)
            logits, st["pcache"] = self._chunk(
                self.params, jnp.asarray(chunk), st["pcache"],
                jnp.int32(off), jnp.asarray(last))
            if self.paged:               # scatter the chunk's blocks now
                cache = self._chunk_scatter(cache, st["pcache"],
                                            jnp.asarray(self._tables[slot]),
                                            jnp.int32(off))
            st["off"] = off + C
            if st["off"] < st["cover"]:
                self._prefill_q.append(slot)
                return cache, rng
            self._prefills.pop(slot)     # last chunk: logits are at eff-1
            if not self.paged:
                cache = self._slot_ins(cache, st["pcache"], jnp.int32(slot))
            return self._activate(st["r"], slot, logits, cache, slots, vecs,
                                  tok, ntok, done, rng, eff=st["eff"],
                                  k=st["k"])

    def _fill_slots(self, slots, queue, cache, vecs, tok, ntok, done, rng):
        """WFQ slot packing: hand each free slot to the backlogged tenant
        with the smallest virtual time whose bucket admits its head
        request.  Returns (cache, rng, granted_count)."""
        scfg = self.scfg
        granted_n = 0
        if not queue:
            return cache, rng, granted_n
        for b in self._buckets.values():
            b.refill()                   # one refill per scheduling round
        occupancy = Counter(s.tenant for s in slots if s is not None)
        self._wfq.note_backlog({r.tenant for r in queue} | set(occupancy))
        # Bucket starvation is counted per scheduling round for every
        # backlogged tenant, independent of slot availability — a starved
        # tenant waiting behind fully occupied slots is still deferred.
        heads: dict[str, Request] = {}
        for r in queue:                  # FIFO head per backlogged tenant
            heads.setdefault(r.tenant, r)
        deferred_round: set[str] = set()
        for tenant, r in heads.items():
            bucket = self._buckets.get(tenant)
            if bucket is not None and \
                    not bucket.can_take(self._admission_cost(r, bucket)):
                self.tenant_stats[tenant]["deferrals"] += 1
                deferred_round.add(tenant)
        slot_cap = self._budget_cap or scfg.max_slots_per_tenant
        for slot in range(scfg.max_batch):
            if slots[slot] is not None or not heads:
                continue
            granted = None
            for tenant in self._wfq.order(heads):
                r = heads[tenant]
                if slot_cap and occupancy[tenant] >= slot_cap:
                    continue             # over its slot budget this tick
                bucket = self._buckets.get(tenant)
                cost = self._admission_cost(r, bucket)
                if bucket is not None and not bucket.can_take(cost):
                    # starved — possibly only mid-round (an earlier grant
                    # drained the bucket), so count if the round-start
                    # scan didn't
                    if tenant not in deferred_round:
                        self.tenant_stats[tenant]["deferrals"] += 1
                        deferred_round.add(tenant)
                    continue
                if self.paged and \
                        self._blocks_for(r) > self._alloc.free_blocks:
                    continue             # pool pressure: wait or try next
                if bucket is not None:
                    bucket.take(cost)
                granted = r
                break
            if granted is None:
                break                    # nothing admissible this round
            for qi, q in enumerate(queue):
                if q is granted:         # remove by identity: rid is not
                    del queue[qi]        # unique and prompt is an ndarray
                    break
            nxt = next((q for q in queue if q.tenant == granted.tenant),
                       None)
            if nxt is None:
                heads.pop(granted.tenant)
            else:
                heads[granted.tenant] = nxt
            self._wfq.grant(granted.tenant,
                            cost=min(granted.max_new_tokens,
                                     scfg.max_new_tokens))
            self.tenant_stats[granted.tenant]["wfq_grants"] += 1
            occupancy[granted.tenant] += 1
            granted_n += 1
            cache, rng = self._start_request(granted, slot, cache, slots,
                                             vecs, tok, ntok, done, rng)
            if slots[slot] is None:      # finished on its first token
                occupancy[granted.tenant] -= 1
        return cache, rng, granted_n

    def _run_continuous(self, requests: list[Request], rng) -> list[Request]:
        scfg = self.scfg
        B = scfg.max_batch
        for r in requests:
            self._check_capacity(r)      # validate up front (ServeError)
        if self.paged:
            layers, kvh, hd, dt = self._pool_geom
            cache = kv_pool_init(layers, self._n_usable, scfg.block_size,
                                 kvh, hd, dtype=dt)
            self._alloc = BlockAllocator(self._n_usable)
            self._tables = np.zeros((B, self._tables_len), np.int32)
            self._slot_blocks: list[list[int]] = [[] for _ in range(B)]
        else:
            cache = self.model.init_cache(B, scfg.kv_cache_len)
        vecs = slot_vectors_init(B)      # per-slot pos/active/tenant
        self._slot_vecs = vecs           # exposed via slot_report()
        self._prefills = {}
        self._prefill_q = deque()
        self._slot_started = [0] * B
        self._slot_seq = 0
        tok = np.zeros((B, 1), np.int32)
        ntok = np.zeros(B, np.int32)
        slots: list[Request | None] = [None] * B
        queue = deque(requests)
        done: list[Request] = []
        starved = 0
        tick = 0
        while queue or vecs["active"].any() or self._prefills:
            tick += 1
            with TraceAnnotation("serve/tick", tick=tick):
                with TraceAnnotation("serve/schedule"):
                    self._enforce_budget(slots, vecs, tok, ntok, queue)
                    cache, rng, granted = self._fill_slots(
                        slots, queue, cache, vecs, tok, ntok, done, rng)
                if self._prefill_q:      # one chunk per tick, interleaved
                    cache, rng = self._advance_chunk(cache, slots, vecs, tok,
                                                     ntok, done, rng)
                if self.paged:           # claim this tick's write blocks
                    with TraceAnnotation("serve/blocks"):
                        for i in np.nonzero(vecs["active"])[0]:
                            if vecs["active"][i]:
                                self._ensure_blocks(int(i), slots, vecs, tok,
                                                    ntok, queue)
                active = np.nonzero(vecs["active"])[0]
                if not len(active):
                    if not queue and not self._prefills:
                        break
                    starved = 0 if granted or self._prefills else \
                        starved + 1
                    if starved > _MAX_STARVED_ROUNDS:
                        # pathological rates (≈0): force progress,
                        # bypassing the bucket, with the queue head
                        r = queue.popleft()
                        cache, rng = self._start_request(
                            r, 0, cache, slots, vecs, tok, ntok, done, rng)
                        starved = 0
                    continue
                starved = 0
                cache, rng = self._decode_tick(tick, active, cache, rng,
                                               slots, vecs, tok, ntok, done,
                                               queue)
        return done

    def _decode_tick(self, tick: int, active, cache, rng, slots, vecs, tok,
                     ntok, done, queue):
        """One decode step over every slot, then its sampled tokens emitted
        (a slot that finishes is freed mid-decode) and the tick observed.
        Returns (cache, rng)."""
        scfg = self.scfg
        span = {"tick": tick, "active": len(active)}
        if self.paged:                   # the share of the tables readable
            span["blocks"] = sum(len(self._slot_blocks[i]) for i in active)
            span["table_blocks"] = self._tables.size
        rows = None
        with TraceAnnotation("serve/decode", **span):
            if self.paged:
                logits, cache, rows = self._step_pool(
                    self.params, jnp.asarray(tok), cache,
                    jnp.asarray(self._tables), jnp.asarray(vecs["pos"]),
                    jnp.asarray(vecs["active"]))
            else:
                logits, cache = self._step_slots(self.params,
                                                 jnp.asarray(tok), cache,
                                                 jnp.asarray(vecs["pos"]))
        with TraceAnnotation("serve/sample") as sample_span:
            rng, k = jax.random.split(rng)
            # the expert rows, where the step has them, come back with
            # the sampled tokens in one transfer
            nxt, rows = jax.device_get(
                (sample(logits[:, -1, :], k, scfg.temperature), rows))
            if rows is not None:
                moe = {"moe_rows": int(rows.sum()),
                       "moe_experts_hit": int((rows > 0).sum())}
                sample_span.set_metadata(**moe)
                self.moe_stats["ticks"] += 1
                for name, v in moe.items():
                    self.moe_stats[name] += v
        with TraceAnnotation("serve/emit", tokens=len(active)):
            for i in active:
                r = slots[i]
                t = int(nxt[i])
                self._emit(r, t, logits, i)
                self.tenant_stats[r.tenant]["occupancy_steps"] += 1
                ntok[i] += 1
                vecs["pos"][i] += 1
                tok[i, 0] = t
                if t == self.eos_id or \
                        ntok[i] >= min(r.max_new_tokens, scfg.max_new_tokens):
                    self._finish(r, done)
                    slots[i] = None                  # freed mid-decode
                    self._release_slot(i, vecs)      # blocks back to pool
        with TraceAnnotation("serve/observe"):
            self._obs_snapshot(active=int(vecs["active"].sum()),
                               queued=len(queue))
        return cache, rng

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------
    def tenant_report(self) -> dict[str, dict[str, float]]:
        """Per-tenant serve accounting: requests, tokens, deferrals, WFQ
        grants and decode-slot occupancy steps."""
        return {t: dict(v) for t, v in self.tenant_stats.items()}

    def slot_report(self) -> list[dict]:
        """Live per-slot view (position, active, tenant name) from the
        slot vectors — the serve-side feed for the per-tenant dashboards
        (ROADMAP): poll during a run to see who holds which slot."""
        vecs = getattr(self, "_slot_vecs", None)
        if vecs is None:
            return []
        names = {i: t for t, i in self._tenant_ids.items()}
        return [{"slot": i, "pos": int(vecs["pos"][i]),
                 "active": bool(vecs["active"][i]),
                 "tenant": names.get(int(vecs["tenant"][i]))}
                for i in range(len(vecs["pos"]))]

    def runtime_counters(self) -> tuple[np.ndarray, tuple[str, ...]]:
        """Serve accounting in per-tenant counter-block layout (rows match
        telemetry counter columns): ops = WFQ slot grants, bytes = served
        tokens, chunks = decode-slot occupancy steps, throttled = bucket
        deferrals.  Lets serve-side QoS land next to the dataplane's
        traced per-tenant runtime counters in dashboards."""
        tenants = tuple(self.tenant_stats)
        ctrs = np.zeros((len(tenants), tl.NUM_COUNTERS), np.float32)
        for i, t in enumerate(tenants):
            s = self.tenant_stats[t]
            ctrs[i, tl.CTR_OPS] = s["wfq_grants"] or s["requests"]
            ctrs[i, tl.CTR_BYTES] = s["tokens"]
            ctrs[i, tl.CTR_CHUNKS] = s["occupancy_steps"]
            ctrs[i, tl.CTR_THROTTLED] = s["deferrals"]
            ctrs[i, tl.CTR_PREEMPTIONS] = s["preemptions"]
            ctrs[i, tl.CTR_RESTORES] = s["restores"]
        return ctrs, tenants

    def decode_compile_count(self) -> int:
        """Decode-step compilations so far (jit cache entries across the
        stripe and pool decode steps) — one per engine, whatever the
        request mix."""
        return sum(f._cache_size()
                   for f in (self._step_slots,
                             getattr(self, "_step_pool", None))
                   if f is not None)


__all__ = ["Engine", "Request", "ServeError", "WFQScheduler", "sample",
           "prompt_bucket"]
