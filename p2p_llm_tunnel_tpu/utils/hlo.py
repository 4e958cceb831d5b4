"""Kernel/launch counting over lowered StableHLO.

A regression that re-splits a kernel's layer body into many kernels should
be visible to a CPU test run, at no chip time.  JAX can lower a
jitted program for the TPU platform from a CPU-only host
(``jit(f).trace(*args).lower(lowering_platforms=("tpu",))``) — that module
is the REAL serving program (Pallas kernels appear as single
``tpu_custom_call`` ops, not their interpret-mode expansion), and its op
counts bound what XLA can launch:

- ``*_major`` counts ops that are kernel ROOTS — dots, custom calls,
  scatters/gathers, dynamic (update) slices, convolutions.  XLA fusion
  can merge elementwise chains INTO these but essentially never merges
  two of them, so major-op count is the tight launch-count proxy.
- ``*_ops`` counts every non-structural op — the upper bound (all
  elementwise ops unfused).

Both are reported; a layer scan appears ONCE in the module (lax.scan
lowers to ``stablehlo.while``), so per-layer-step numbers come from the
innermost while body that contains a dot — the layer scan.

What uses it today: tests/test_ragged_prefill.py holds the ragged prefill
kernel's claim (ONE custom call a layer) with ``decode_launch_report``.
Nothing on the serving path does; the module goes or stays with the ragged
kernel's contest on the chip (ROADMAP Design 6).
"""

from __future__ import annotations

from typing import Dict, Optional

#: Ops that root a kernel launch: XLA fuses elementwise producers and
#: consumers into them, but (essentially) never merges two of these into
#: one kernel.  dynamic_slice/dynamic_update_slice of the GB-scale cache
#: count — they launch as copy/update kernels when feeding a custom call.
MAJOR_OPS = frozenset({
    "stablehlo.dot_general",
    "stablehlo.dot",
    "stablehlo.convolution",
    "stablehlo.custom_call",
    "stablehlo.scatter",
    "stablehlo.gather",
    "stablehlo.dynamic_slice",
    "stablehlo.dynamic_update_slice",
    "stablehlo.sort",
    "stablehlo.reduce_window",
    "stablehlo.fft",
})

#: Structural / zero-work ops excluded from every count.
_SKIP_OPS = frozenset({
    "builtin.module",
    "func.func",
    "func.return",
    "func.call",
    "stablehlo.return",
    "stablehlo.constant",
    "stablehlo.tuple",
    "stablehlo.get_tuple_element",
    "stablehlo.optimization_barrier",
})


def _walk(op):
    yield op
    for region in op.regions:
        for block in region:
            for inner in block:
                yield from _walk(inner)


def _func_index(module_op):
    funcs = {}
    for op in _walk(module_op):
        if op.operation.name == "func.func":
            name = str(op.operation.attributes["sym_name"]).strip('"')
            funcs[name] = op
    return funcs


def _walk_resolved(op, funcs, _stack=None):
    """Walk regions AND through ``func.call`` — JAX outlines scan bodies
    into private functions, so the layer body is a callee, not inline."""
    _stack = _stack or ()
    yield op
    if op.operation.name == "func.call":
        callee = str(op.operation.attributes["callee"]).lstrip("@").strip('"')
        target = funcs.get(callee)
        if target is not None and callee not in _stack:
            for region in target.regions:
                for block in region:
                    for inner in block:
                        yield from _walk_resolved(
                            inner, funcs, _stack + (callee,)
                        )
        return
    for region in op.regions:
        for block in region:
            for inner in block:
                yield from _walk_resolved(inner, funcs, _stack)


def _count(ops) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for op in ops:
        name = op.operation.name
        if name in _SKIP_OPS:
            continue
        counts[name] = counts.get(name, 0) + 1
    return counts


def launch_counts(lowered) -> Dict[str, int]:
    """Launch-proxy counts for a ``jax.stages.Lowered`` program.

    Returns ``total_ops`` / ``total_major`` / ``pallas_calls`` for the
    whole module, plus ``layer_body_ops`` / ``layer_body_major`` /
    ``layer_body_pallas`` for the innermost ``stablehlo.while`` body that
    contains a dot (calls resolved) — in a decode burst that is the layer
    scan, so those numbers are per decode LAYER-STEP (zero when the
    program has no such loop, e.g. an unscanned toy).
    """
    module = lowered.compiler_ir(dialect="stablehlo")
    funcs = _func_index(module.operation)
    # Entry function only, calls resolved — private outlined bodies must
    # not be double-counted as siblings of their call sites.
    entry = funcs.get("main") or next(iter(funcs.values()), None)
    if entry is None:
        return {k: 0 for k in (
            "total_ops", "total_major", "pallas_calls",
            "layer_body_ops", "layer_body_major", "layer_body_pallas",
        )}
    all_ops = list(_walk_resolved(entry, funcs))
    totals = _count(all_ops)

    def _contains_dot(op) -> bool:
        return any(
            o.operation.name in ("stablehlo.dot_general", "stablehlo.dot")
            for o in _walk_resolved(op, funcs)
        )

    # Innermost dotted while: a while whose resolved body has a dot but no
    # NESTED while that has one (the steps scan nests the layer scan).
    layer_counts: Dict[str, int] = {}
    whiles = [op for op in all_ops if op.operation.name == "stablehlo.while"]
    for w in whiles:
        sub = list(_walk_resolved(w, funcs))
        nested = [
            o for o in sub
            if o.operation.name == "stablehlo.while" and o is not w
        ]
        if _contains_dot(w) and not any(_contains_dot(n) for n in nested):
            layer_counts = _count(o for o in sub if o is not w)
            break

    def major(counts: Dict[str, int]) -> int:
        return sum(n for name, n in counts.items() if name in MAJOR_OPS)

    def pallas(counts: Dict[str, int]) -> int:
        return counts.get("stablehlo.custom_call", 0)

    return {
        "total_ops": sum(totals.values()),
        "total_major": major(totals),
        "pallas_calls": pallas(totals),
        "layer_body_ops": sum(layer_counts.values()),
        "layer_body_major": major(layer_counts),
        "layer_body_pallas": pallas(layer_counts),
    }


def lower_for_tpu(jitted, *args, **kwargs):
    """Lower a jitted callable for the TPU platform from ANY host.

    On a CPU-only host this produces the genuine TPU serving program
    (Mosaic kernels serialize into ``tpu_custom_call`` without needing a
    chip); on a TPU host it is the native lowering.  Raises whatever the
    lowering raises — callers on diagnostic paths catch and degrade.
    """
    return jitted.trace(*args, **kwargs).lower(lowering_platforms=("tpu",))


def decode_launch_report(jitted, *args, **kwargs) -> Optional[Dict[str, int]]:
    """``launch_counts`` of a TPU-lowered program, or None when the host
    cannot lower it (old jaxlib, untileable shapes, ...)."""
    try:
        return launch_counts(lower_for_tpu(jitted, *args, **kwargs))
    except Exception:
        return None
