"""Percent of the window's pool decode ticks that copied only the picked token
ids to the host, not the logits: device_pick_ticks / decode_steps of the
engine's own counters. None where the engine does not count them."""

LAYER = "engine (serving/engine.py)"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"
WORKLOADS = ["qwen2.5-3b.prefill_heavy", "qwen2.5-3b.decode_heavy"]


def read(run):
    st = run.counters.get("stats")
    if not st or "device_pick_ticks" not in st or not st["decode_steps"]:
        return None
    return 100.0 * st["device_pick_ticks"] / st["decode_steps"]
