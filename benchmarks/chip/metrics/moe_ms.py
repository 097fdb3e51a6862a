"""Device time per train step of the ops under the ``moe/`` name scopes
(route, experts, shared), from the trace."""


def read(w):
    m = (w.trace or {}).get("moe")
    if not m or not m["steps"] or not m["moe_s"]:
        return None
    return 1e3 * m["moe_s"] / m["steps"]
