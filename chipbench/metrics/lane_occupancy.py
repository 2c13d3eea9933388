"""Scheduler and admission: active decode lanes over lane slots, summed
over the window's engine steps (the engine's lane_step_sum / lane_slot_sum)."""


def read(rec):
    slots = rec.counters.get("lane_slot_sum", 0)
    return 100.0 * rec.counters["lane_step_sum"] / slots if slots else None
