"""Multi-tenant serving policy: SLO classes, priority, admission quotas.

One FleetRouter, N models, M replicas each: a *tenant* is one model
plus the service contract its traffic runs under. The contract is a
:class:`TenantSpec` — the SLO class picks the posture (a latency
tenant wants small queues and fast answers, a batch tenant wants
throughput and tolerates queueing), priority orders tenants for the
degradation ladder (serving/autoscale.py sheds the LOWEST priority
first when the fleet is pinned at max scale), and the admission quota
is the weighted-fair bound: each tenant may hold at most
``weight x MXTPU_FLEET_TENANT_QUOTA`` requests in flight, so a batch
tenant that floods the fleet saturates its OWN quota and sheds — it
can never occupy the queue space a latency tenant's traffic needs
(per-tenant queue bounds instead of a shared FIFO; with per-tenant
replica groups there is no shared dequeue to reorder, the bound IS the
fairness mechanism).

Every tenant gets its own registry series —
``serving::tenant::<name>::latency_ms`` (histogram, p50/p99 at
snapshot), ``::shed``, ``::slo_violations`` — so per-tenant SLO
compliance is scrape-able and shows in ``tools/telemetry.py diff``
between two snapshots.

SLO-violation accounting: a completed request whose client-observed
latency exceeds ``slo_p99_ms`` counts one violation, as does a request
the fleet failed after admission (sheds are counted separately — a
shed was never admitted, the client was told to back off).
"""
from __future__ import annotations

import threading

from .. import config
from ..base import MXNetError

__all__ = ["TenantSpec", "SLO_CLASSES", "DEFAULT_TENANT"]

DEFAULT_TENANT = "default"
SLO_CLASSES = ("latency", "throughput", "batch")

# per-class posture defaults: priority orders the degradation ladder
# (lowest sheds first), weight scales the admission quota
_CLASS_DEFAULTS = {
    "latency": {"priority": 2, "weight": 4},
    "throughput": {"priority": 1, "weight": 2},
    "batch": {"priority": 0, "weight": 1},
}


class TenantSpec:
    """One tenant's model + service contract.

    Parameters
    ----------
    name : str
        Tenant id — routing key for ``submit(tenant=...)`` and the
        registry series label.
    factory : callable () -> DynamicBatcher
        Builds one replica of this tenant's model (same contract as
        ``FleetRouter(replica_factory=...)``); spin-ups and hot-swap
        replacements reuse it.
    slo_class : {"latency", "throughput", "batch"}
        Service posture; fills ``priority``/``weight`` defaults.
    priority : int, optional
        Degradation order: the LOWEST-priority tenant is shed first
        when the fleet is overloaded at max scale.
    weight : int, optional
        Weighted-fair share: scales the admission quota.
    quota : int, optional
        Max in-flight admitted requests before this tenant's submits
        shed (default ``weight x MXTPU_FLEET_TENANT_QUOTA``).
    replicas : int
        UNIFIED replica count the group starts with (each prefills AND
        decodes). May be 0 for a disaggregated group.
    prefill_replicas / decode_replicas : int, optional
        Disaggregated prefill/decode formation (round 21, defaults
        ``MXTPU_FLEET_ROLE_PREFILL`` / ``MXTPU_FLEET_ROLE_DECODE``):
        with BOTH > 0 the group runs role-split — prefill replicas
        fill KV lanes and hand each one to a decode replica
        (``DecodeBatcher.set_handoff``/``adopt``), so a long prompt's
        prefill never lands between another stream's tokens. The
        factory is called with ``role=`` when it accepts the kwarg.
    min_replicas / max_replicas : int, optional
        Autoscaler bounds for this group (default the
        ``MXTPU_FLEET_{MIN,MAX}_REPLICAS`` env vars).
    slo_p99_ms : float, optional
        Latency SLO target: completed requests slower than this count
        as violations in the tenant's registry series. None = no
        latency target (throughput/batch tenants typically).
    """

    def __init__(self, name, factory=None, slo_class="latency",
                 priority=None, weight=None, quota=None, replicas=1,
                 min_replicas=None, max_replicas=None, slo_p99_ms=None,
                 prefill_replicas=None, decode_replicas=None):
        if slo_class not in SLO_CLASSES:
            raise MXNetError(
                f"tenant '{name}': slo_class must be one of "
                f"{SLO_CLASSES}, got {slo_class!r}")
        self.prefill_replicas = int(
            prefill_replicas if prefill_replicas is not None
            else config.get("MXTPU_FLEET_ROLE_PREFILL", 0))
        self.decode_replicas = int(
            decode_replicas if decode_replicas is not None
            else config.get("MXTPU_FLEET_ROLE_DECODE", 0))
        if (self.prefill_replicas > 0) != (self.decode_replicas > 0):
            raise MXNetError(
                f"tenant '{name}': disaggregation needs BOTH "
                f"prefill_replicas and decode_replicas > 0 (got "
                f"{self.prefill_replicas}/{self.decode_replicas}) — a "
                "prefill replica without a decode sink would decode "
                "locally, which is just a unified replica")
        if int(replicas) + self.prefill_replicas + \
                self.decode_replicas < 1:
            raise MXNetError(f"tenant '{name}' needs >= 1 replica")
        cls = _CLASS_DEFAULTS[slo_class]
        self.name = str(name)
        self.factory = factory
        self.slo_class = slo_class
        self.priority = int(cls["priority"] if priority is None
                            else priority)
        self.weight = int(cls["weight"] if weight is None else weight)
        base = int(config.get("MXTPU_FLEET_TENANT_QUOTA", 16))
        self.quota = int(quota if quota is not None
                         else max(1, self.weight * base))
        self.replicas = int(replicas)
        self.min_replicas = int(
            min_replicas if min_replicas is not None
            else config.get("MXTPU_FLEET_MIN_REPLICAS", 1))
        self.max_replicas = int(
            max_replicas if max_replicas is not None
            else config.get("MXTPU_FLEET_MAX_REPLICAS", 4))
        self.slo_p99_ms = None if slo_p99_ms is None else float(slo_p99_ms)

    @property
    def disaggregated(self):
        """True when this group runs the split prefill/decode
        formation (both role counts > 0)."""
        return self.prefill_replicas > 0 and self.decode_replicas > 0

    @property
    def total_replicas(self):
        """Initial formation size across every role."""
        return self.replicas + self.prefill_replicas + \
            self.decode_replicas

    def __repr__(self):
        return (f"TenantSpec({self.name!r}, slo_class={self.slo_class!r},"
                f" priority={self.priority}, weight={self.weight},"
                f" quota={self.quota}, replicas={self.replicas},"
                f" prefill={self.prefill_replicas},"
                f" decode={self.decode_replicas})")


class _TenantLedger:
    """Router-side runtime state for one tenant: the in-flight quota
    gate, counters, latency window, and the degradation-shed flag the
    autoscaler's ladder flips. All mutation under the router's lock
    except the registry handles (atomic already)."""

    def __init__(self, spec):
        self.spec = spec
        self.inflight = 0          # admitted, not yet finished
        self.routed = 0
        self.served = 0
        self.shed = 0
        self.slo_violations = 0
        self.swaps = 0             # completed weight hot-swaps
        self.lats = []             # recent client-observed latencies (s)
        self.degraded_shed = False  # ladder rung 1: admission closed
        from ..telemetry import registry as treg
        pfx = f"serving::tenant::{spec.name}::"
        self._h_lat = treg.histogram(pfx + "latency_ms")
        self._c_shed = treg.counter(pfx + "shed")
        self._c_slo = treg.counter(pfx + "slo_violations")

    # callers hold the router lock for the counter fields; registry
    # handles are safe outside it
    def note_shed(self):
        self.shed += 1
        self._c_shed.inc()

    def note_done(self, lat_s, error, lat_window):
        if error is None:
            self.served += 1
            self.lats.append(lat_s)
            if len(self.lats) > lat_window:
                del self.lats[:len(self.lats) - lat_window]
            self._h_lat.observe(lat_s * 1e3)
            if self.spec.slo_p99_ms is not None and \
                    lat_s * 1e3 > self.spec.slo_p99_ms:
                self.slo_violations += 1
                self._c_slo.inc()
        else:
            # admitted but failed: the SLO was violated for real
            self.slo_violations += 1
            self._c_slo.inc()

    def report(self, reset=False):
        lats = sorted(self.lats)

        def _pct(q):
            if not lats:
                return None
            return round(lats[min(len(lats) - 1,
                                  int(q * (len(lats) - 1)))] * 1e3, 3)

        out = {
            "slo_class": self.spec.slo_class,
            "priority": self.spec.priority,
            "weight": self.spec.weight,
            "quota": self.spec.quota,
            "slo_p99_ms": self.spec.slo_p99_ms,
            "inflight": self.inflight,
            "routed": self.routed,
            "served": self.served,
            "shed": self.shed,
            "slo_violations": self.slo_violations,
            "swaps": self.swaps,
            "degraded_shed": self.degraded_shed,
            "p50_ms": _pct(0.50),
            "p99_ms": _pct(0.99),
        }
        if reset:
            self.routed = self.served = self.shed = 0
            self.slo_violations = 0
            self.lats = []
        return out
