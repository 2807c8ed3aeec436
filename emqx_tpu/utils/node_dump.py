"""Node state dump for support/debugging.

Parity: apps/emqx/src/emqx_node_dump.erl + bin/node_dump — a one-call
snapshot of everything an operator attaches to a support ticket: config
(secrets redacted), broker/session/route gauges, component statuses,
alarms, metrics, and versions. Exposed at ``GET /api/v5/node_dump`` and
``emqx_tpu_ctl node_dump``.
"""

from __future__ import annotations

import sys
import time
from typing import Dict

# exact-ish credential field names — NOT bare "key", which would also hide
# TLS key-file PATHS the dump exists to show
REDACT_KEYS = (
    "password", "passwd", "secret", "jwt_secret", "token", "api_key", "cookie"
)
# subtrees whose dict VALUES are secrets keyed by arbitrary names
REDACT_VALUE_MAPS = (("dashboard", "admins"), ("psk", "identities"))


def _redact(obj, path=()):
    if isinstance(obj, dict):
        if path in REDACT_VALUE_MAPS:
            return {k: "*****" for k in obj}
        return {
            k: (
                "*****"
                if k.lower() in REDACT_KEYS and v
                else _redact(v, path + (k,))
            )
            for k, v in obj.items()
        }
    if isinstance(obj, list):
        return [_redact(v, path) for v in obj]
    return obj


def collect(app) -> Dict:
    from emqx_tpu import __version__
    from emqx_tpu.config.schema import to_dict

    broker = app.broker
    dump: Dict = {
        "at": time.time(),
        "versions": {
            "emqx_tpu": __version__,
            "python": sys.version.split()[0],
        },
        "config": _redact(to_dict(app.config)),
        "broker": {
            "connections": app.cm.channel_count(),
            "detached_sessions": app.cm.detached_count(),
            "subscriptions": broker.subscription_count(),
            "routes": len(broker.router),
            "shared_subscriptions": broker.shared.count(),
            "shared_groups": len(broker.grouptab),
            "retained": len(app.retainer),
            "route_index": {
                "filters": len(broker.router.index),
                "residual": broker.router.index.residual_count,
                "shapes": broker.router.index.shapes.num_active_shapes(),
            },
        },
        "metrics": broker.metrics.snapshot(),
        "alarms": app.alarms.list(None),
        "components": {
            "gateways": app.gateways.list() if app.gateways else [],
            "bridges": app.bridges.list() if app.bridges else [],
            "plugins": app.plugins.list() if app.plugins else [],
            "exhook": app.exhook.info() if app.exhook else [],
            "license": app.license.license.info(),
        },
        "rules": [
            {"id": r.id, "enabled": r.enabled, "metrics": r.metrics.as_dict()}
            for r in app.rule_engine.rules()
        ],
    }
    # only report devices when JAX is ALREADY initialized — first-touch
    # backend init can take seconds and this runs on the serving loop
    if "jax" in sys.modules:
        try:
            dump["devices"] = [str(d) for d in sys.modules["jax"].devices()]
        except Exception as e:
            dump["devices"] = [f"unavailable: {e}"]
    else:
        dump["devices"] = ["jax not initialized"]
    return dump
