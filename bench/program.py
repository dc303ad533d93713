"""What the drivers ask of the system under test alike: the tuning database a
cell names, the dispatch tiers a runtime served, and a check that the
program's model is the configuration file's."""
from __future__ import annotations

import os


def tuning_db(run):
    """The database the cell names (a path under the benchmark's directory),
    or an empty one: every site then resolves at the heuristic tier."""
    from repro.core.database import TuningDatabase

    path = run.cell.get("tuning_db")
    return TuningDatabase(os.path.join(run.bench, path) if path else None)


def tiers(rt):
    """{tier: dispatches} over every site ``rt`` resolved (at trace time)."""
    out = {}
    for per in rt.telemetry.snapshot()["by_key"].values():
        for tier, n in per.items():
            out[tier] = out.get(tier, 0) + n
    return out


def check_model(c, cfg):
    """Raise unless the program's ArchConfig ``c`` is the configuration file
    ``cfg``'s dense Qwen2 block at its sizes."""
    got = {
        "num_hidden_layers": c.num_layers, "hidden_size": c.d_model,
        "num_attention_heads": c.num_heads, "num_key_value_heads": c.num_kv_heads,
        "head_dim": c.hd, "intermediate_size": c.d_ff, "vocab_size": c.vocab_size,
        "rope_theta": c.rope_theta, "rms_norm_eps": c.norm_eps, "torch_dtype": c.dtype,
        "qkv_bias": c.qkv_bias,
    }
    bad = {k: (v, cfg[k]) for k, v in got.items() if v != cfg[k]}
    if c.ffn_kind != "swiglu" or c.num_experts or c.window or any(
            spec.mixer != "attn" for seg in c.segments() for spec in seg.pattern):
        bad["block"] = (c.ffn_kind, c.num_experts, c.window)
    if bad:
        raise RuntimeError(f"the program's model departs from the configuration "
                           f"(program, file): {bad}")
