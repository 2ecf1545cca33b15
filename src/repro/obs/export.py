"""Exporter: the JSON metrics document.

The JSON document is :meth:`repro.obs.registry.Snapshot.as_dict` plus
optional sidecars (the planner ledger, a serving summary) — the payload
``launch/serve.py --metrics-json`` writes and the CI smoke parses.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional

from repro.obs.registry import MetricsRegistry, Snapshot

__all__ = ["to_json", "metrics_document", "unified_snapshot"]


def unified_snapshot(*extra: "MetricsRegistry | Snapshot") -> Snapshot:
    """The process-global registry's snapshot merged with any extra
    registries/snapshots (per-runtime serving registries, typically)."""
    from repro.obs import registry as _reg
    snap = _reg.get_registry().snapshot()
    for e in extra:
        if e is None:
            continue
        snap = snap.merge(e if isinstance(e, Snapshot) else e.snapshot())
    return snap


def metrics_document(snap: Snapshot, *, ledger: bool = True,
                     extra: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
    """The full JSON document: snapshot + plan-ledger summary + extras."""
    doc = snap.as_dict()
    if ledger:
        from repro.core import plan as _plan
        doc["plan_ledger"] = _plan.get_ledger().summary()
    if extra:
        doc.update(extra)
    return doc


def to_json(snap: Snapshot, *, ledger: bool = True,
            extra: Optional[Dict[str, Any]] = None, indent: int = 2) -> str:
    return json.dumps(metrics_document(snap, ledger=ledger, extra=extra),
                      indent=indent, sort_keys=True)
