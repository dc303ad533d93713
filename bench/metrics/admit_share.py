"""Percent of the traced window spent in the engine's admissions: the summed
``serve.admit`` spans (prefill, first sample, cache insert), in which the
decode pool stands still."""
from spans import span_share

LAYER = "engine (serving/engine.py)"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(run):
    return span_share(run, "serve.admit")
