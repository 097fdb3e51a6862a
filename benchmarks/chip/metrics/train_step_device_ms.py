"""Device time of the jitted train step per call, from the trace."""


def read(w):
    p = (w.trace or {}).get("programs", {}).get("jit_train_step")
    if not p or not p["calls"]:
        return None
    return 1e3 * p["device_s"] / p["calls"]
