"""Extract roofline terms from a compiled dry-run artifact.

``cost_analysis()`` supplies HLO_FLOPs / HLO_bytes (per-device, post-SPMD).
Collective bytes are NOT in cost_analysis, so we parse the partitioned HLO
text: every instruction line is ``%name = TYPE opcode(%operand, ...)``; we
index result types by name so collective operand sizes can be resolved.

Byte-counting conventions (per device, recorded per op kind):

* all-gather          -> result bytes (ring: each chip passes ~the full
                          gathered tensor through its link)
* all-reduce          -> 2 x result bytes (reduce-scatter + all-gather phases)
* reduce-scatter      -> operand bytes (full pre-reduction tensor streams by)
* all-to-all          -> result bytes
* collective-permute  -> result bytes

The §Roofline collective term is then  sum(weighted bytes) / ICI_BW  —
algebraically identical to the assignment's
``collective_bytes / (chips x link_bw)`` with collective_bytes summed over
all chips of the SPMD program.
"""

from __future__ import annotations

import re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "c64": 8,
    "s64": 8, "u64": 8, "f64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# opcode -> (use operand bytes?, multiplier)
_WEIGHT = {
    "all-gather": (False, 1.0),
    "all-reduce": (False, 2.0),
    "reduce-scatter": (True, 1.0),
    "all-to-all": (False, 1.0),
    "collective-permute": (False, 1.0),
}


def type_bytes(type_str: str) -> int:
    """Total bytes of an HLO type string (handles tuples)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        size = _DTYPE_BYTES.get(dt)
        if size is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


def _split_type_op(rhs: str):
    """rhs: 'TYPE opcode(...)' -> (type_str, opcode) or None."""
    # TYPE is either '(...)' tuple or a token like 'bf16[8,16]{1,0}'
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                type_str = rhs[: i + 1]
                rest = rhs[i + 1:].strip()
                break
        else:
            return None
    else:
        parts = rhs.split(None, 1)
        if len(parts) != 2:
            return None
        type_str, rest = parts
    op = rest.split("(", 1)[0].strip()
    return type_str, op


_OPERAND_RE = re.compile(r"%?([\w.\-]+)")


def collective_stats(hlo_text: str) -> dict:
    """Parse HLO text -> {"counts": {op: n}, "bytes": {op: weighted_bytes},
    "total_bytes": float, "raw_bytes": {op: result_bytes}}."""
    types: dict[str, str] = {}
    collect_lines: list[tuple[str, str, str]] = []   # (name, type, full rhs)
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        st = _split_type_op(rhs)
        if st is None:
            continue
        type_str, op = st
        types[name] = type_str
        base_op = op.split(".")[0]          # e.g. all-gather-start
        for c in COLLECTIVES:
            if base_op == c or base_op == c + "-start":
                collect_lines.append((name, c, rhs))
                break

    counts: dict[str, int] = {c: 0 for c in COLLECTIVES}
    weighted: dict[str, float] = {c: 0.0 for c in COLLECTIVES}
    raw: dict[str, float] = {c: 0.0 for c in COLLECTIVES}
    for name, c, rhs in collect_lines:
        st = _split_type_op(rhs)
        result_bytes = type_bytes(st[0])
        # -start ops wrap results in a tuple (operand, result[, scratch]);
        # count the real payload once.
        if "-start" in rhs.split("(", 1)[0]:
            result_bytes = result_bytes / 2
        use_operand, mult = _WEIGHT[c]
        nbytes = result_bytes
        if use_operand:
            args = rhs.split("(", 1)[1] if "(" in rhs else ""
            op_bytes = 0
            for om in _OPERAND_RE.finditer(args.split(")")[0]):
                t = types.get(om.group(1))
                if t is not None:
                    op_bytes += type_bytes(t)
            nbytes = op_bytes or result_bytes
        counts[c] += 1
        raw[c] += result_bytes
        weighted[c] += mult * nbytes
    return {
        "counts": {k: v for k, v in counts.items() if v},
        "bytes": {k: v for k, v in weighted.items() if v},
        "raw_bytes": {k: v for k, v in raw.items() if v},
        "total_bytes": sum(weighted.values()),
    }


def pool_copies(hlo_text: str, num_blocks: int) -> list[str]:
    """The ``copy`` instructions of ``hlo_text`` whose result is shaped
    like a paged KV pool of ``num_blocks`` blocks: a layer's pool
    ``[nb, ...]``, one sliced out of the stacked pools ``[1, nb, ...]``,
    or the stacked pools whole ``[L, nb, ...]``.  Returns each as
    ``"name = type"``; a decode step that reads and writes its pools in
    place has none."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        st = _split_type_op(rhs)
        if st is None or st[1] not in ("copy", "copy-start"):
            continue
        sm = _SHAPE_RE.search(st[0])
        dims = [int(d) for d in sm.group(2).split(",") if d] if sm else []
        if num_blocks in dims[:2]:
            out.append(f"{name} = {st[0]}")
    return out


def cost_summary(compiled) -> dict:
    """Pull flops / bytes out of compiled.cost_analysis() (per-device)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:       # noqa: BLE001
        return {}
    out = {}
    for k in ("flops", "bytes accessed", "transcendentals", "optimal_seconds"):
        if k in ca:
            out[k.replace(" ", "_")] = float(ca[k])
    # per-memory-space byte entries (bytes accessed0{}, operand 0 etc.)
    return out


def memory_summary(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:       # noqa: BLE001
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        out["total_nonalias_bytes"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - 2 * out.get("alias_size_in_bytes", 0))
    return out
