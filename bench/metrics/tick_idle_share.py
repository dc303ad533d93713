"""Percent of the traced window in which the device was idle inside the
engine's ``serve.tick`` spans: the idle time that the per-tick host path
(building inputs, dispatch, the logits fetch, sampling, retirement) causes."""
from spans import idle_share_in

LAYER = "engine (serving/engine.py)"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_tokens_per_s"


def read(run):
    return idle_share_in(run, "serve.tick")
