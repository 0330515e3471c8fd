"""The Converged Dataplane — the paper's contribution, adapted to JAX/TPU.

Every communication edge in the framework is issued through a
:class:`Dataplane`:

* under **pjit/GSPMD** the model code calls :meth:`constrain` with logical
  axis names; the dataplane resolves them against its sharding rules and
  emits ``with_sharding_constraint`` — the compiler materializes the
  collectives.  The dataplane is the single control point that sees (and
  records, and may refuse) every one of these edges.
* inside **shard_map** (explicit paths: gradient sync, MoE dispatch option,
  perftest/NPB benchmarks, the verbs layer) the model code calls
  :meth:`psum` / :meth:`all_gather` / :meth:`reduce_scatter` /
  :meth:`all_to_all` / :meth:`ppermute`, which lower to ``jax.lax``
  collectives *after* passing the mediation layer.

Mediation is one composable artifact: ``self.pipeline`` — a
:class:`~repro.core.mediation.MediationPipeline` compiled by
:func:`~repro.core.mediation.build_pipeline` from the mode presets,
technique toggles and policy set.  The GSPMD constraint path, the five
explicit collectives and the verbs layer (core/verbs.py) all run it, so a
mode or policy ablation applies identically everywhere.

Runtime state follows one uniform convention: every explicit collective
takes an optional ``state`` pytree (from :meth:`runtime_init`) and
returns ``(out, state)`` — always a pair, state ``None`` when not
threaded.  The state carries per-tenant counter blocks and policy state
(QoS token buckets), so quota/QoS have *runtime* teeth inside traced
code, not just at trace time.

Three modes (paper Fig. 2):

====== ============= ========= ============ =========================
mode   kernel-bypass zero-copy polling      policies enforced
====== ============= ========= ============ =========================
bypass yes           yes       yes          none (OS has no control)
cord   **no**        yes       yes          all configured policies
socket **no**        **no**    **no**       all + heavy stack cost
====== ============= ========= ============ =========================

Technique toggles in :class:`DataplaneConfig` override the mode presets so
that the paper's Fig. 1 ablations ("remove one technique at a time") can be
reproduced exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import DataplaneConfig
from repro.core import techniques as tech
from repro.core import telemetry as tl
from repro.core.mediation import build_pipeline, runtime_state_init
from repro.core.mr import MRRegistry
from repro.core.policies import (
    Policy,
    PolicyContext,
    PolicyViolation,
    QoSPolicy,
    QuotaPolicy,
    SecurityPolicy,
    TelemetryPolicy,
)

# ---------------------------------------------------------------------------
# Mode presets: (kernel_bypass, zero_copy, polling, enforce_policies)
# ---------------------------------------------------------------------------

_MODE_PRESETS = {
    "bypass": dict(kernel_bypass=True, zero_copy=True, polling=True, enforce=False),
    "cord": dict(kernel_bypass=False, zero_copy=True, polling=True, enforce=True),
    "socket": dict(kernel_bypass=False, zero_copy=False, polling=False, enforce=True),
}

_POLICY_FACTORIES: dict[str, Callable[[], Policy]] = {
    "telemetry": TelemetryPolicy,
    "security": SecurityPolicy,
    "quota": QuotaPolicy,
    "qos": QoSPolicy,
}


class Dataplane:
    """The narrow waist: all framework communication flows through here."""

    def __init__(
        self,
        cfg: DataplaneConfig | None = None,
        mesh: Mesh | None = None,
        rules: dict[str, Any] | None = None,
        tenant: str = "default",
        tenants: Sequence[str] | None = None,
        policies: Sequence[Policy] | None = None,
    ) -> None:
        self.cfg = cfg or DataplaneConfig()
        self.mesh = mesh
        self.rules = dict(rules or {})
        self.tenant = tenant
        names = list(tenants if tenants is not None else self.cfg.tenants)
        if tenant not in names:
            names.insert(0, tenant)
        self.tenants: tuple[str, ...] = tuple(names)
        if self.cfg.mode not in _MODE_PRESETS:
            raise ValueError(f"unknown dataplane mode {self.cfg.mode!r}")
        preset = _MODE_PRESETS[self.cfg.mode]
        # Effective techniques: mode preset AND config toggle, so the fig-1
        # ablations can "remove" a technique from any mode.
        self.kernel_bypass = preset["kernel_bypass"] and self.cfg.kernel_bypass
        self.zero_copy = preset["zero_copy"] and self.cfg.zero_copy
        self.polling = preset["polling"] and self.cfg.polling
        self.enforce = preset["enforce"]
        if policies is not None:
            self.policies = list(policies)
        else:
            self.policies = [_POLICY_FACTORIES[p]() for p in self.cfg.policies]
        self._telemetry = next(
            (p.telemetry for p in self.policies if isinstance(p, TelemetryPolicy)),
            tl.Telemetry(enabled=False))
        self._security = next(
            (p for p in self.policies if isinstance(p, SecurityPolicy)), None)
        self.registry: MRRegistry = (self._security.registry
                                     if self._security else MRRegistry())
        # The single mediation artifact every path compiles against.
        self.pipeline = build_pipeline(self)
        if self.cfg.emulate_costs:
            # calibrate the delay primitives NOW (eagerly) — calling them
            # for the first time under a trace would stage the probe jit.
            tech.calibrate()
            if self.pipeline.pallas:
                from repro.kernels.dataplane import kernel_calibrate
                kernel_calibrate()

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    @property
    def telemetry(self) -> tl.Telemetry:
        return self._telemetry

    @property
    def mode(self) -> str:
        return self.cfg.mode

    def with_mode(self, mode: str) -> "Dataplane":
        return Dataplane(dataclasses.replace(self.cfg, mode=mode),
                         mesh=self.mesh, rules=self.rules, tenant=self.tenant,
                         tenants=self.tenants)

    def reset(self) -> None:
        for p in self.policies:
            p.reset()

    # ------------------------------------------------------------------
    # per-tenant runtime state
    # ------------------------------------------------------------------
    def tenant_index(self, tenant: str | None = None) -> int:
        """Static index of a tenant in this dataplane's tenant table."""
        name = tenant or self.tenant
        try:
            return self.tenants.index(name)
        except ValueError:
            raise KeyError(
                f"unknown tenant {name!r}; known tenants: {self.tenants}")

    def runtime_init(self) -> dict:
        """Per-tenant runtime-state pytree: thread it through shard_map
        bodies with the uniform ``(x, state)`` convention."""
        return runtime_state_init(self.tenants, self.policies)

    def runtime_report(self, state) -> dict:
        """Host-side per-tenant view of a runtime-state pytree."""
        return tl.tenant_counters_report(state["counters"], self.tenants)

    # ------------------------------------------------------------------
    # mediation core
    # ------------------------------------------------------------------
    def _policy_pass(self, rec: tl.OpRecord, operand, mr_name: str | None,
                     tenant: str) -> None:
        """Trace-time policy enforcement (the kernel looking at the WQE)."""
        if not self.enforce:
            return
        ctx = PolicyContext(rec=rec, tenant=tenant, mr_name=mr_name,
                            operand=operand)
        for p in self.policies:
            p.on_op(ctx)    # raises PolicyViolation to refuse the op

    def _record(self, kind: str, tag: str, x, axes, qos: str = "default",
                mr: str | None = None, count: int = 1,
                tenant: str | None = None,
                precharged: bool = False) -> tl.OpRecord:
        shape, dtype = tl.describe(x)
        rec = tl.OpRecord(kind=kind, tag=tag, bytes=tl.nbytes(x),
                          axes=tl.normalize_axes(axes),
                          shape=shape, dtype=dtype, mode=self.cfg.mode,
                          qos=qos, count=count, precharged=precharged)
        self._policy_pass(rec, x, mr, tenant or self.tenant)
        return rec

    def _mediate(self, collective, kind: str, x, axis, tag: str, *,
                 mr: str | None, state, qos: str, tenant: str | None,
                 precharged: bool = False):
        """One dataplane op: record → pipeline.send → collective →
        pipeline.complete.  All five explicit collectives are this."""
        rec = self._record(kind, tag, x, axis, qos, mr, tenant=tenant,
                           precharged=precharged)
        ti = self.tenant_index(tenant)
        x, state = self.pipeline.send(x, rec, state, ti)
        out = collective(x)
        out, state = self.pipeline.complete(out, rec, state, ti)
        return out, state

    # ------------------------------------------------------------------
    # GSPMD-mode mediation: logical sharding constraints
    # ------------------------------------------------------------------
    def spec(self, names: Sequence[str | None | tuple]) -> P:
        """Resolve logical axis names to a PartitionSpec via the rules.

        A mesh axis may appear at most once in a spec — later duplicates
        are dropped (first occurrence wins)."""
        out = []
        used: set[str] = set()

        def take(axes):
            kept = [a for a in axes if a not in used]
            used.update(kept)
            return kept

        for n in names:
            if n is None:
                out.append(None)
                continue
            subs = n if isinstance(n, (tuple, list)) else [n]
            merged: list[str] = []
            for sub in subs:
                r = self.rules.get(sub)
                if r is None:
                    continue
                merged.extend(take(list(r) if isinstance(r, (tuple, list))
                                  else [r]))
            out.append(tuple(merged) if len(merged) > 1
                       else (merged[0] if merged else None))
        return P(*out)

    def sharding(self, names: Sequence[str | None | tuple]) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(names))

    def constrain(self, x: jax.Array, names: Sequence[str | None | tuple],
                  tag: str = "constraint", qos: str = "default",
                  tenant: str | None = None) -> jax.Array:
        """Issue a sharding edge through the dataplane (GSPMD mode).

        Runs the same mediation pipeline as the explicit collectives
        (send side only — GSPMD materializes the completion); no runtime
        state can be threaded through a pjit constraint, so stateful
        stages are inert here."""
        if self.mesh is None:
            return x
        spec = self.spec(names)
        rec = self._record("constraint", tag, x, spec, qos, tenant=tenant)
        x, _ = self.pipeline.send(x, rec, None, self.tenant_index(tenant))
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    # ------------------------------------------------------------------
    # Explicit collectives (inside shard_map) — uniform (out, state)
    # ------------------------------------------------------------------
    def psum(self, x, axis, tag: str = "psum", mr: str | None = None,
             state=None, qos: str = "default", tenant: str | None = None,
             precharged: bool = False):
        """``precharged=True`` marks an op whose QoS tokens were already
        debited at chunk granularity by the issuer (chunked_psum's
        preemption path) — the token-bucket stage skips it."""
        return self._mediate(lambda v: jax.lax.psum(v, axis), "all_reduce",
                             x, axis, tag, mr=mr, state=state, qos=qos,
                             tenant=tenant, precharged=precharged)

    def all_gather(self, x, axis, tag: str = "all_gather", *, gather_axis: int = 0,
                   tiled: bool = False, mr: str | None = None,
                   state=None, qos: str = "default", tenant: str | None = None):
        return self._mediate(
            lambda v: jax.lax.all_gather(v, axis, axis=gather_axis, tiled=tiled),
            "all_gather", x, axis, tag, mr=mr, state=state, qos=qos,
            tenant=tenant)

    def reduce_scatter(self, x, axis, tag: str = "reduce_scatter", *,
                       scatter_axis: int = 0, mr: str | None = None,
                       state=None, qos: str = "default",
                       tenant: str | None = None):
        return self._mediate(
            lambda v: jax.lax.psum_scatter(v, axis,
                                           scatter_dimension=scatter_axis,
                                           tiled=True),
            "reduce_scatter", x, axis, tag, mr=mr, state=state, qos=qos,
            tenant=tenant)

    def all_to_all(self, x, axis, tag: str = "all_to_all", *, split_axis: int = 0,
                   concat_axis: int = 0, mr: str | None = None,
                   state=None, qos: str = "default", tenant: str | None = None):
        return self._mediate(
            lambda v: jax.lax.all_to_all(v, axis, split_axis=split_axis,
                                         concat_axis=concat_axis, tiled=True),
            "all_to_all", x, axis, tag, mr=mr, state=state, qos=qos,
            tenant=tenant)

    def ppermute(self, x, axis, perm, tag: str = "ppermute",
                 mr: str | None = None, state=None, qos: str = "default",
                 tenant: str | None = None):
        return self._mediate(
            lambda v: jax.lax.ppermute(v, axis, perm), "collective_permute",
            x, axis, tag, mr=mr, state=state, qos=qos, tenant=tenant)

    # ------------------------------------------------------------------
    # control plane
    # ------------------------------------------------------------------
    def reg_mr(self, name: str, x, tenant: str | None = None):
        """Control-plane memory registration (ioctl path in the paper)."""
        return self.registry.reg_mr(name, x, tenant or self.tenant)

    def reg_pytree(self, prefix: str, tree, tenant: str | None = None) -> int:
        return self.registry.reg_pytree(prefix, tree, tenant or self.tenant)


def make_dataplane(cfg: DataplaneConfig | None = None, mesh: Mesh | None = None,
                   rules: dict[str, Any] | None = None, **kw) -> Dataplane:
    return Dataplane(cfg, mesh=mesh, rules=rules, **kw)


__all__ = ["Dataplane", "make_dataplane", "PolicyViolation"]
