"""The composable mediation pipeline — CoRD's "kernel on the data path"
as one reusable artifact.

The paper's claim is that OS-level control over the RDMA dataplane is
cheap because mediation is built from a handful of composable techniques.
This module is that composition: a :class:`MediationPipeline` is an
ordered list of :class:`MediationStage` objects, compiled once per
:class:`~repro.core.dataplane.Dataplane` from its mode, technique toggles
and policy set by :func:`build_pipeline`.  Every path that crosses the
dataplane — GSPMD sharding constraints, explicit shard_map collectives,
and the ibverbs-style point-to-point layer — runs the *same* pipeline, so
mode/policy ablations apply identically everywhere.

Stages (declared order):

  ============== ========================================== ==============
  stage          emulates                                   side
  ============== ========================================== ==============
  syscall-cost   user→kernel crossing (kernel bypass off)   send
  socket-stack   full kernel network stack + per-byte cost  send
  staged-copy    bounce-buffer copies (zero copy off)       send+complete
  interrupt-wait interrupt delivery + wakeup (polling off)  complete
  token-bucket   per-tenant QoS rate limiting (QoSPolicy)   send
  counter-bump   per-tenant runtime accounting + quota mark send
  ============== ========================================== ==============

Every stage preserves values bit-exactly: mediation changes *cost* and
*state*, never results.

Runtime state is a pytree dict threaded through shard_map bodies with the
uniform ``(x, state)`` convention:

    state = dp.runtime_init()              # {"counters": (T, C) f32, ...}
    out, state = dp.psum(x, "data", state=state)

``state=None`` disables all stateful stages (GSPMD constraint paths,
where no state can be threaded, pass None).

:class:`HostTokenBucket` is the host-side mirror of the traced token
bucket, used by the serving engine for tenant admission control.

The per-tenant counter blocks the ``counter-bump`` stage maintains are
the feed for the observability timelines (core/obs.py): snapshot
``dp.runtime_report(state)`` between steps to stream this pipeline's
accounting into rate series and panels.  docs/architecture.md maps the
stages to the paper's techniques; docs/observability.md defines each
counter.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import techniques as tech
from repro.core import telemetry as tl
from repro.core.policies import Policy, QoSPolicy, QuotaPolicy


# ---------------------------------------------------------------------------
# Stage protocol
# ---------------------------------------------------------------------------

class MediationStage:
    """One composable mediation technique.

    ``send`` runs on the issue side (before the NIC DMA / collective);
    ``complete`` on the completion side.  Both must return ``x``
    value-identical — a stage may delay, copy, account or throttle, never
    alter.  ``send_delay_iters`` / ``complete_delay_iters`` report the
    stage's static serial-delay cost so benchmark harnesses can aggregate
    per-op mediation work without reimplementing the cost model.

    ``stateful = False`` declares a *pure cost* stage: its entire effect is
    the static delay iterations and staged-copy passes it reports, so a
    fused pipeline may sum those across stages and emit ONE delay chain
    and ONE copy pass per side instead of running the stage hooks.
    Stateful stages (accounting, throttling, anything a subclass adds)
    always run their hooks in declared order."""

    name = "stage"
    stateful = True

    def send(self, x, rec: tl.OpRecord, state, tenant_idx: int):
        return x, state

    def complete(self, x, rec: tl.OpRecord, state, tenant_idx: int):
        return x, state

    def send_delay_iters(self, rec: tl.OpRecord) -> int:
        return 0

    def complete_delay_iters(self, rec: tl.OpRecord) -> int:
        return 0

    def send_copies(self, rec: tl.OpRecord) -> int:
        return 0

    def complete_copies(self, rec: tl.OpRecord) -> int:
        return 0

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.name!r}>"


class SyscallCostStage(MediationStage):
    """The user→kernel crossing paid per op when kernel bypass is off."""

    name = "syscall-cost"
    stateful = False

    def __init__(self, syscall_ns: float):
        self.syscall_ns = float(syscall_ns)

    def send(self, x, rec, state, tenant_idx):
        return tech.delay_chain(x, self.send_delay_iters(rec)), state

    def send_delay_iters(self, rec):
        return tech.iters_for_ns(self.syscall_ns)


class SocketStackStage(MediationStage):
    """The extra cost of the full kernel network stack (socket mode):
    a fixed per-op term plus a per-payload-byte term (IPoIB bandwidth
    degradation)."""

    name = "socket-stack"
    stateful = False

    def __init__(self, stack_ns: float, ns_per_byte: float):
        self.stack_ns = float(stack_ns)
        self.ns_per_byte = float(ns_per_byte)

    def send(self, x, rec, state, tenant_idx):
        return tech.delay_chain(x, self.send_delay_iters(rec)), state

    def send_delay_iters(self, rec):
        return tech.iters_for_ns(self.stack_ns + rec.bytes * self.ns_per_byte)


class StagedCopyStage(MediationStage):
    """Bounce-buffer copies on both sides when zero copy is removed.

    With ``pallas=True`` the copies are the real Pallas bounce-buffer
    kernel (``kernels/dataplane``): double-buffered DMA through a VMEM
    scratch slot instead of the XLA roll/barrier emulation.  Output is
    bit-identical either way."""

    name = "staged-copy"
    stateful = False

    def __init__(self, copies: int = 1, pallas: bool = False):
        self.copies = int(copies)
        self.pallas = bool(pallas)

    def _copy(self, x):
        if self.pallas:
            from repro.kernels import dataplane as dk
            return dk.bounce_copy(x, copies=self.copies)
        return tech.staged_copy(x, copies=self.copies)

    def send(self, x, rec, state, tenant_idx):
        return self._copy(x), state

    def complete(self, x, rec, state, tenant_idx):
        return self._copy(x), state

    def send_copies(self, rec):
        return self.copies

    def complete_copies(self, rec):
        return self.copies


class InterruptWaitStage(MediationStage):
    """Wait-for-event completion: interrupt delivery + wakeup instead of
    busy polling."""

    name = "interrupt-wait"
    stateful = False

    def __init__(self, interrupt_us: float):
        self.interrupt_us = float(interrupt_us)

    def complete(self, x, rec, state, tenant_idx):
        return tech.delay_chain(x, self.complete_delay_iters(rec)), state

    def complete_delay_iters(self, rec):
        return tech.iters_for_ns(self.interrupt_us * 1e3)


class TokenBucketStage(MediationStage):
    """Per-tenant QoS throttling: delegates to QoSPolicy.on_op_runtime
    (the traced token bucket)."""

    name = "token-bucket"

    def __init__(self, policy: QoSPolicy, tenants: tuple[str, ...]):
        self.policy = policy
        self.tenants = tenants

    def send(self, x, rec, state, tenant_idx):
        if rec.precharged:
            # chunk-granular preemption (core/chunking.py) already
            # debited this op's tokens chunk by chunk — charging the
            # assembled op again would double-bill the tenant.
            return x, state
        return self.policy.on_op_runtime(x, state, rec,
                                         self.tenants[tenant_idx], tenant_idx)


class CounterBumpStage(MediationStage):
    """The 'syscall body': bump the issuing tenant's runtime counters, then
    let the quota policy mark over-budget traffic."""

    name = "counter-bump"

    def __init__(self, tenants: tuple[str, ...],
                 quota: QuotaPolicy | None = None):
        self.tenants = tenants
        self.quota = quota

    def send(self, x, rec, state, tenant_idx):
        if state is None or "counters" not in state:
            return x, state
        ctrs = tl.tenant_counters_bump(state["counters"], tenant_idx,
                                       ops=rec.count,
                                       bytes=rec.bytes * rec.count)
        state = {**state, "counters": ctrs}
        if self.quota is not None:
            x, state = self.quota.on_op_runtime(
                x, state, rec, self.tenants[tenant_idx], tenant_idx)
        return x, state


# ---------------------------------------------------------------------------
# Pipeline
# ---------------------------------------------------------------------------

class MediationPipeline:
    """An ordered composition of mediation stages.

    ``send``/``complete`` apply the stages in declared order.  An empty
    pipeline (bypass mode) is the identity — the OS is off the data path.

    With ``fused=True`` (the default) the pure-cost stages are *fused*:
    their static delay iterations are summed into ONE ``delay_chain`` and
    their bounce-buffer passes into ONE ``staged_copy`` per side, instead
    of one chain/copy per stage.  That shrinks the per-op HLO on every
    dataplane edge (one while-loop + one barrier pair instead of N) while
    staying bit-identical — every fused stage is value-preserving by
    contract, and total serial cost is unchanged because delay iterations
    add linearly.  Stateful stages (token-bucket, counter-bump, custom
    subclasses) still run their hooks in declared order.

    With ``pallas=True`` a fused pure-cost side is ONE Pallas kernel
    launch (``mediated_cost`` in kernels/dataplane): the summed delay
    iterations burn on the scalar core between a chunk's DMA copy-in
    and copy-out, and the summed bounce passes are real double-buffered
    VMEM copies — measured-mode mediation cost becomes a hardware
    measurement instead of an XLA emulation, still bit-identical."""

    def __init__(self, stages=(), fused: bool = True, pallas: bool = False):
        self.stages: tuple[MediationStage, ...] = tuple(stages)
        self.fused = bool(fused)
        self.pallas = bool(pallas)

    @property
    def stage_names(self) -> tuple[str, ...]:
        return tuple(s.name for s in self.stages)

    def _pure_cost(self, rec, side: str) -> tuple[int, int]:
        iters = sum(getattr(s, f"{side}_delay_iters")(rec)
                    for s in self.stages if not s.stateful)
        copies = sum(getattr(s, f"{side}_copies")(rec)
                     for s in self.stages if not s.stateful)
        return iters, copies

    def _kernel_ctr_bump(self, state, tenant_idx, kernel_iters,
                         kernel_copies):
        """Land a side's in-kernel cost work in the tenant counter block
        (``kernel_iters``/``kernel_copies``)."""
        if state is None or "counters" not in state:
            return state
        ctrs = tl.tenant_counters_bump(state["counters"], tenant_idx,
                                       kernel_iters=kernel_iters,
                                       kernel_copies=kernel_copies)
        return {**state, "counters": ctrs}

    def _static_cost_bump(self, x, rec, state, tenant_idx, side: str):
        """The XLA-emulation (and unfused) half of the kernel-cost
        accounting: bump the totals the cost kernel's SMEM counters
        *would* sum to for this payload, so reports are bit-identical
        across pallas on/off and fused/unfused."""
        iters, copies = self._pure_cost(rec, side)
        if not (iters or copies) or state is None or "counters" not in state:
            return state
        from repro.kernels.dataplane import kernel_cost_totals
        kit, kcp = kernel_cost_totals(x.size, iters, copies)
        return self._kernel_ctr_bump(state, tenant_idx, kit, kcp)

    def _fused_side(self, x, rec, state, tenant_idx, side: str):
        iters, copies = self._pure_cost(rec, side)
        if self.pallas and (iters or copies):
            from repro.kernels import dataplane as dk
            x, kctrs = dk.mediated_cost(x, dk.rescale_iters(iters), copies)
            # the kernel's SMEM cost totals, landed in the tenant
            # block: what the hardware actually burned/copied
            state = self._kernel_ctr_bump(
                state, tenant_idx,
                jnp.sum(kctrs[:, dk.COST_ITERS]),
                jnp.sum(kctrs[:, dk.COST_COPIES]))
        else:
            if iters:
                x = tech.delay_chain(x, iters)
            if copies:
                x = tech.staged_copy(x, copies=copies)
            state = self._static_cost_bump(x, rec, state, tenant_idx, side)
        for s in self.stages:
            if s.stateful:
                x, state = getattr(s, side)(x, rec, state, tenant_idx)
        return x, state

    def send(self, x, rec: tl.OpRecord, state=None, tenant_idx: int = 0):
        if self.fused:
            return self._fused_side(x, rec, state, tenant_idx, "send")
        for s in self.stages:
            x, state = s.send(x, rec, state, tenant_idx)
        return x, self._static_cost_bump(x, rec, state, tenant_idx, "send")

    def complete(self, x, rec: tl.OpRecord, state=None, tenant_idx: int = 0):
        if self.fused:
            return self._fused_side(x, rec, state, tenant_idx, "complete")
        for s in self.stages:
            x, state = s.complete(x, rec, state, tenant_idx)
        return x, self._static_cost_bump(x, rec, state, tenant_idx,
                                         "complete")

    def send_delay_iters(self, rec: tl.OpRecord) -> int:
        return sum(s.send_delay_iters(rec) for s in self.stages)

    def complete_delay_iters(self, rec: tl.OpRecord) -> int:
        return sum(s.complete_delay_iters(rec) for s in self.stages)

    def send_copies(self, rec: tl.OpRecord) -> int:
        return sum(s.send_copies(rec) for s in self.stages)

    def complete_copies(self, rec: tl.OpRecord) -> int:
        return sum(s.complete_copies(rec) for s in self.stages)

    def __repr__(self) -> str:
        fused = "" if self.fused else " unfused"
        return f"MediationPipeline{self.stage_names}{fused}"


def build_pipeline(dp) -> MediationPipeline:
    """Compile a dataplane's effective techniques + policies into stages.

    ``dp`` duck-types a Dataplane: cfg, mode, kernel_bypass, zero_copy,
    polling, enforce, policies, tenants."""
    from repro.kernels.dataplane import use_pallas_dataplane
    cfg = dp.cfg
    pallas = use_pallas_dataplane(getattr(cfg, "pallas_dataplane", "auto"))
    stages: list[MediationStage] = []
    mediated = not dp.kernel_bypass        # the OS sees this traffic
    if mediated and cfg.emulate_costs:
        stages.append(SyscallCostStage(cfg.syscall_cost_ns))
        if dp.mode == "socket":
            stages.append(SocketStackStage(cfg.socket_stack_ns,
                                           cfg.socket_ns_per_byte))
    if not dp.zero_copy:
        stages.append(StagedCopyStage(pallas=pallas))
    if not dp.polling and cfg.emulate_costs:
        stages.append(InterruptWaitStage(cfg.interrupt_cost_us))
    if dp.enforce:
        qos = next((p for p in dp.policies
                    if isinstance(p, QoSPolicy) and p.rates), None)
        if qos is not None:
            stages.append(TokenBucketStage(qos, dp.tenants))
    if mediated:
        quota = next((p for p in dp.policies
                      if isinstance(p, QuotaPolicy)), None) \
            if dp.enforce else None
        stages.append(CounterBumpStage(dp.tenants, quota))
    return MediationPipeline(stages,
                             fused=getattr(cfg, "fuse_mediation", True),
                             pallas=pallas)


def runtime_state_init(tenants: tuple[str, ...],
                       policies: list[Policy]) -> dict:
    """The per-tenant runtime-state pytree threaded through shard_map:
    a counter block plus each stateful policy's slice keyed by name."""
    state = {"counters": tl.tenant_counters_init(len(tenants))}
    for p in policies:
        ps = p.init_state(len(tenants))
        if ps is not None:
            state[p.name] = ps
    return state


# ---------------------------------------------------------------------------
# Host-side token bucket (serving admission control)
# ---------------------------------------------------------------------------

class HostTokenBucket:
    """Pure-python mirror of the traced QoS token bucket.

    The serving engine refills explicitly once per batching round (the
    host-side analogue of per-op refill), keeping admission deterministic
    and clock-free for tests.  Serve-side admission charges *prompt
    tokens* per request — matching the traced bucket's byte-proportional
    debits — so ``from_policy`` scales rate and burst by ``scale`` tokens
    per traced-rate unit."""

    def __init__(self, rate: float, burst: float):
        self.rate = float(rate)
        self.burst = float(burst)
        self.tokens = float(burst)

    def refill(self) -> None:
        self.tokens = min(self.tokens + self.rate, self.burst)

    def can_take(self, n: float = 1.0) -> bool:
        return self.tokens >= n

    def take(self, n: float = 1.0) -> bool:
        if self.tokens >= n:
            self.tokens -= n
            return True
        return False

    @classmethod
    def from_policy(cls, qos: QoSPolicy | None,
                    scale: float = 1.0) -> dict[str, "HostTokenBucket"]:
        if qos is None:
            return {}
        return {t: cls(rate * scale, qos.burst * scale)
                for t, rate in qos.rates.items() if rate > 0}


__all__ = [
    "MediationStage", "MediationPipeline", "build_pipeline",
    "runtime_state_init", "SyscallCostStage", "SocketStackStage",
    "StagedCopyStage", "InterruptWaitStage", "TokenBucketStage",
    "CounterBumpStage", "HostTokenBucket",
]
