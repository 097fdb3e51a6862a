"""The window's mean, over steps, of the busiest held expert's slots
over an even share of the held slots (1 is even), from the program's
``moe_max_expert_slots`` and ``moe_held_slots`` counters."""


def read(w):
    c = w.counts
    if "moe_imbalance_sum" not in c or not c["steps"]:
        return None
    return c["moe_imbalance_sum"] / c["steps"]
