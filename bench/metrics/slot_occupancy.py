"""Percent of the decode pool's slot-steps in the window that produced a kept
token: slot_steps_active / (slot_steps_active + slot_steps_idle) of the
engine's own counters."""

LAYER = "engine (serving/engine.py)"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"
WORKLOADS = ["qwen2.5-3b.prefill_heavy", "qwen2.5-3b.decode_heavy"]


def read(run):
    st = run.counters.get("stats")
    if not st:
        return None
    steps = st["slot_steps_active"] + st["slot_steps_idle"]
    return 100.0 * st["slot_steps_active"] / steps if steps else None
