"""Operations, bytes and least time of one kernel call, from its shapes.

Multiply-add = 2 operations. Bytes are what the call must read and write at
least: every operand once and every result once. Least time is the larger
of operations over the chip's peak rate and bytes over its memory
bandwidth; which of the two is larger says what bounds the call. Adapted
from the system's own per-site roofline model, per Pallas call.

Each Pallas kernel (the function a pallas_call runs) that a roofline metric
reads has a file of its own, ``bench/kernels/<kernel>.py``, found by the
kernel's name: ``FAMILY``, the family whose metric reads it
(``<family>_roofline.*``), and ``work(ins, outs)``, its (operations, bytes)
from the operand and result types. A kernel with no file belongs to no
metric, and asking for its work raises: nothing falls back.
"""
from __future__ import annotations

import functools
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))

BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
         "f8e4m3fn": 1, "pred": 1, "s64": 8, "f64": 8}
_JAX_TO_HLO = {"bfloat16": "bf16", "float16": "f16", "float32": "f32",
               "int32": "s32", "uint32": "u32", "int8": "s8", "uint8": "u8",
               "bool": "pred", "int64": "s64", "float64": "f64",
               "float8_e4m3fn": "f8e4m3fn"}


def peaks(device_kind):
    """The peak table's row for ``device_kind``; a kind it lacks raises."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _n(shape):
    out = 1
    for d in shape:
        out *= d
    return out


def nbytes(types):
    """Bytes of a list of (dtype, shape)."""
    return sum(_n(s) * BYTES[dt] for dt, s in types)


def attended_pairs(sq, sk):
    """(query, key) pairs a causal call attends: queries sit at the end of
    the key axis."""
    off = sk - sq
    return sq * off + sq * (sq + 1) // 2


@functools.lru_cache(maxsize=None)
def kernel(name, bench=HERE):
    """The module of kernel ``name`` (``bench/kernels/<name>.py``), or None
    where the kernel has no file; looked up once a run (a trace holds
    hundreds of thousands of calls)."""
    import importlib.util

    path = os.path.join(bench, "kernels", name + ".py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location("bench_kernel_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name, bench=HERE):
    """The family whose roofline metric reads kernel ``name``; None for a
    kernel with no file."""
    mod = kernel(name, bench)
    return mod.FAMILY if mod else None


def work(name, ins, outs, bench=HERE):
    """(operations, bytes) of one call of kernel ``name`` with operand types
    ``ins`` and result types ``outs``, each a list of (dtype, shape). The
    operand types are those before any padding to the kernel's tiles: the
    work the call has to do, not the zeros it also multiplies."""
    mod = kernel(name, bench)
    if mod is None:
        raise KeyError(f"no operation count for kernel {name!r}: no file "
                       f"{os.path.join(bench, 'kernels', name + '.py')}")
    return mod.work(ins, outs)


def least_time(name, ins, outs, peak, bench=HERE):
    """(seconds, bound) of one call: bound is "compute" or "memory"."""
    ops, nb = work(name, ins, outs, bench)
    t_c, t_m = ops / peak["bf16_flops"], nb / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# ---------------------------------------------------------------------------
# kernel calls in a program and in a trace
# ---------------------------------------------------------------------------


def _aval_type(aval):
    return _JAX_TO_HLO[str(aval.dtype)], tuple(int(d) for d in aval.shape)


def signature(ins, outs):
    fmt = lambda ts: ";".join(f"{dt}[{','.join(map(str, s))}]" for dt, s in ts)
    return f"{fmt(ins)}->{fmt(outs)}"


def pallas_kernels(jaxpr, seen=None):
    """{signature: (kernel name, operand types before padding)} of every
    pallas_call in a jaxpr and in its sub-jaxprs. The signature is of the
    call as it runs (padded operands), which is what a trace shows."""
    seen = set() if seen is None else seen
    padded = {}
    out = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pad" or eqn.params.get("name") == "_pad":
            padded[eqn.outvars[0]] = eqn.invars[0]      # lax.pad, or jnp.pad's jit
        if eqn.primitive.name == "pallas_call":
            name = eqn.params.get("name") or eqn.params["jaxpr"].debug_info.func_name
            ins = [_aval_type(v.aval) for v in eqn.invars]
            outs = [_aval_type(v.aval) for v in eqn.outvars]
            true_ins = [_aval_type(padded.get(v, v).aval) for v in eqn.invars]
            out[signature(ins, outs)] = (name, true_ins)
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns") and id(sub) not in seen:
                    seen.add(id(sub))
                    out.update(pallas_kernels(sub, seen))
    return out


_TYPE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")


def _close(text, i):
    """Index of the parenthesis that closes the one at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j
    return len(text)


def _types(text):
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _TYPE.findall(text) if dt in BYTES]


def parse_op(text):
    """(instruction, opcode, operand types, result types) of one device op
    event, whose name is its HLO instruction text."""
    if " = " not in text:
        return text, text, [], []
    name, rest = text.split(" = ", 1)
    name = name.strip().lstrip("%")
    if rest.startswith("("):
        end = _close(rest, 0)
        result, tail = rest[: end + 1], rest[end + 1:]
    else:
        result, _, tail = rest.partition(" ")
        tail = " " + tail
    m = re.match(r"\s*([a-zA-Z][\w\-]*)\(", tail)
    if not m:
        return name, "", [], _types(result)
    opcode = m.group(1)
    start = tail.index("(", m.start(1))
    operands = tail[start: _close(tail, start) + 1]
    return name, opcode, _types(operands), _types(result)


def is_kernel_call(text):
    return 'custom_call_target="tpu_custom_call"' in text
