"""Percent of the window's prefill tokens that were padding: 1 - true prompt
tokens / the engine's prefill_tokens (prompts right-padded to power-of-two
buckets)."""

LAYER = "engine (serving/engine.py)"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_tokens_per_s"
WORKLOADS = ["qwen2.5-3b.prefill_heavy"]


def read(run):
    st = run.counters.get("stats")
    if not st or not st["prefill_tokens"]:
        return None
    true = sum(L for L, _ in run.counters["requests"])
    return 100.0 * (1.0 - true / st["prefill_tokens"])
