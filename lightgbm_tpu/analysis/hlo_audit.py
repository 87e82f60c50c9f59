"""HLO-level audit (layer 2b of the analyzer).

What tracing cannot see, the compiled executable can: whether declared
buffer donations were actually honored by XLA (the
``input_output_alias`` table in the HLO module header — a donation XLA
silently drops turns the scores/bag-mask rebinding into a full copy per
block), f64 types that appear only after lowering, and host custom-calls
hiding in compiled code.

Everything here consumes an AOT artifact from
``fn.lower(*ShapeDtypeStruct_mirrors).compile()`` — the obs/costmodel.py
extraction discipline: AOT lowering shares no cache with the executing
programs, so an audit run never recompiles or perturbs training or
serving executables.
"""
from __future__ import annotations

import re
import warnings
from typing import Any, Dict, List, Sequence, Tuple

# one entry of the HLO header's input_output_alias table:
#   { {0}: (3, {}, may-alias), {1}: (8, {}, must-alias) }
# reads "output tuple index {0} aliases parameter 3".
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{[^}]*\}(?:,\s*([a-z-]+))?\)")


def hlo_text(compiled: Any) -> str:
    """The HLO text of a compiled executable (AOT ``.compile()`` result
    or anything exposing ``as_text()``)."""
    if hasattr(compiled, "as_text"):
        return compiled.as_text()
    return str(compiled)


def input_output_aliases(text: str) -> List[Dict[str, Any]]:
    """Parse the ``input_output_alias={...}`` table from an HLO module
    header.  Returns ``[{"output_index", "param_number", "kind"}, ...]``
    — empty when the module declares no aliasing (i.e. every donation
    was dropped)."""
    start = text.find("input_output_alias={")
    if start < 0:
        return []
    i = text.index("{", start)
    depth, j = 0, i
    while j < len(text):
        if text[j] == "{":
            depth += 1
        elif text[j] == "}":
            depth -= 1
            if depth == 0:
                break
        j += 1
    seg = text[i:j + 1]
    out: List[Dict[str, Any]] = []
    for m in _ALIAS_ENTRY_RE.finditer(seg):
        idx = [int(x) for x in m.group(1).replace(",", " ").split()]
        out.append({"output_index": idx,
                    "param_number": int(m.group(2)),
                    "kind": m.group(3) or "may-alias"})
    return out


def flat_param_ranges(args: Sequence[Any]) -> List[Tuple[int, int]]:
    """Per-python-argument ``[start, end)`` ranges into the flattened
    HLO parameter list — how ``donate_argnums`` positions map onto the
    ``param_number`` column of the alias table."""
    import jax
    ranges: List[Tuple[int, int]] = []
    off = 0
    for a in args:
        n = len(jax.tree_util.tree_leaves(a))
        ranges.append((off, off + n))
        off += n
    return ranges


def audit_donation(fn: Any, args: Sequence[Any],
                   donate_argnums: Sequence[int]) -> Dict[str, Any]:
    """Lower ``fn`` AOT with ``donate_argnums`` and verify every donated
    leaf is input-output aliased in the compiled executable.

    ``args`` are ShapeDtypeStruct mirrors of the real call (use
    ``Booster.train_block_sds``), so the audited program has the exact
    signature of the dispatched one.  Donation is forced here even on
    backends where the executing jit gates it off (CPU) — XLA records
    the alias table regardless, which is what makes the check portable
    to the TPU-less CI host.

    Lowering uses ``keep_unused=True``: without it jit drops dead
    argument leaves (a disabled bagging path's keys, for instance) and
    the HLO parameter numbering no longer matches the flattened python
    signature the donation indices are defined against.
    """
    import jax
    with warnings.catch_warnings():
        # a donation XLA cannot use warns at compile time; the alias
        # TABLE below is what reports it
        warnings.simplefilter("ignore")
        jitted = jax.jit(fn, donate_argnums=tuple(donate_argnums),
                         keep_unused=True)
        compiled = jitted.lower(*args).compile()
    text = hlo_text(compiled)
    aliases = input_output_aliases(text)
    aliased_params = {a["param_number"] for a in aliases}
    ranges = flat_param_ranges(args)
    donated_params: List[int] = []
    for argnum in donate_argnums:
        lo, hi = ranges[argnum]
        donated_params.extend(range(lo, hi))
    missing = sorted(set(donated_params) - aliased_params)
    return {
        "donate_argnums": list(donate_argnums),
        "donated_params": donated_params,
        "aliased_params": sorted(aliased_params),
        "missing": missing,
        "aliases": aliases,
        "ok": not missing,
    }


def count_f64(text: str) -> int:
    """``f64`` tensor types in HLO text — catches f64 that appears only
    after lowering (constant folding, upcasts the jaxpr does not show)."""
    return len(re.findall(r"\bf64\[", text))


def host_custom_calls(text: str) -> List[str]:
    """Custom-call targets in the HLO — host callbacks lower to these;
    any hit in a hot-path entry is a dispatch-pipeline stall."""
    return re.findall(r'custom_call_target="([^"]+)"', text)
