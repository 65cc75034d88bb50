"""Program inspection: pretty printer + graphviz export
(reference: python/paddle/fluid/debugger.py — draw_block_graphviz /
pprint_program_codes)."""

from __future__ import annotations

from typing import Optional

from paddle_tpu.framework import Program


def _compile_report_lines(program: Program) -> list:
    """Annotation header from the program's latest compile report (if the
    telemetry plane recorded one): the listing then answers not just
    "what ops" but "what do they cost compiled"."""
    from paddle_tpu import monitor

    rep = monitor.compile_reports().get(f"program{program._uid}")
    if rep is None:
        return []

    def _fmt(v, unit=""):
        if v is None:
            return "null"
        if unit == "B":
            return f"{int(v):,} B"
        return f"{v:,.0f}" if isinstance(v, float) else f"{v:,}"

    # .get throughout: record_compile_report accepts (and never rejects)
    # hand-built reports, and the debugging utility must not crash on one
    return [
        f"compile report (v{rep.get('v')}, source={rep.get('source')}, "
        f"backend={rep.get('backend')}):",
        f"  flops={_fmt(rep.get('flops'))} "
        f"bytes_accessed={_fmt(rep.get('bytes_accessed'))}",
        f"  peak={_fmt(rep.get('peak_bytes'), 'B')} "
        f"(args={_fmt(rep.get('argument_bytes'), 'B')} "
        f"out={_fmt(rep.get('output_bytes'), 'B')} "
        f"temp={_fmt(rep.get('temp_bytes'), 'B')})",
        f"  n_ops={rep.get('n_ops')} "
        f"compile_ms={_fmt(rep.get('compile_ms'))} "
        f"analysis_ms={_fmt(rep.get('analysis_ms'))}",
    ]


def _time_attribution_lines() -> list:
    """Annotation from the time-attribution plane: the latest step
    record's phase breakdown plus the rolling boundedness verdict. Not
    program-keyed (step records aren't) — it describes the most recent
    executor step, which during single-program debugging is the one
    being inspected."""
    from paddle_tpu import monitor

    recs = monitor.recent_steps(1)
    phases = recs[0].get("phases") if recs else None
    bound = monitor.boundedness()
    if phases is None and bound is None:
        return []
    lines = []
    if phases is not None:
        lines.append(
            "time attribution (last step): " + " ".join(
                f"{k}={phases[k]:.2f}ms" for k in
                ("feed", "dispatch", "device", "fetch") if k in phases))
    if bound is not None:
        s = bound["shares"]
        lines.append(
            f"  boundedness: {bound['verdict']} over last "
            f"{bound['steps']} steps (input {s['input']:.0%} dispatch "
            f"{s['dispatch']:.0%} device {s['device']:.0%})")
    return lines


def _numerics_lines(program: Program):
    """(header lines, {op idx -> marker}) from the numerics plane's
    latest NaN/Inf provenance record for this program (if any)."""
    from paddle_tpu import numerics

    rec = numerics.provenance_for(program._uid)
    if rec is None:
        return [], {}
    step = rec.get("nan_step")
    step = rec.get("step") if step is None else step
    header = [
        f"numerics provenance (v{rec.get('v')}): first non-finite at "
        f"op [{rec.get('op_idx')}] {rec.get('op_type')} -> "
        f"'{rec.get('var')}' (step {step}, "
        f"nonfinite={rec.get('nonfinite'):.0f}, "
        f"maxabs={rec.get('maxabs'):.3g})",
    ]
    marks = {rec.get("op_idx"): "   !! first non-finite "
                                f"(var {rec.get('var')}, step {step})"}
    return header, marks


def _lint_lines(program: Program):
    """(header lines, {op idx -> marker}) from the static verifier's
    latest findings for this program (analysis.findings_for): severity
    counts plus one line per warning/error, with error sites marked
    inline on the op listing."""
    from paddle_tpu import analysis

    rec = analysis.findings_for(program._uid)
    if rec is None:
        return [], {}
    lines = [f"static lint (v{rec.get('v')}, "
             f"{rec.get('lint_ms', 0.0):.1f}ms): "
             f"{analysis.format_counts(rec.get('counts') or {})}"]
    marks = {}
    for f in rec.get("findings", ()):
        if f.get("severity") not in ("warning", "error"):
            continue
        lines.append(f"  [{f.get('severity')}] {f.get('check')} @ "
                     f"{f.get('site')}: {f.get('message')}")
        if f.get("hint"):
            lines.append(f"    fix: {f['hint']}")
        if f.get("severity") == "error" and f.get("op_idx") is not None \
                and f.get("block_idx") == 0:
            marks.setdefault(
                f["op_idx"],
                f"   !! lint: {f.get('check')} ('{f.get('var')}')")
    return lines, marks


def pprint_program(program: Program, with_shapes: bool = True,
                   with_compile_report: bool = True,
                   with_numerics: bool = True,
                   with_timeline: bool = True,
                   with_lint: bool = True) -> str:
    """Readable multi-block listing of a Program's vars and ops,
    prefixed with the latest compile-report annotation when telemetry
    recorded one (``with_compile_report=False`` opts out), the latest
    NaN/Inf provenance record when the numerics plane holds one — the
    offending op line is marked inline (``with_numerics=False`` opts
    out) — the latest step's phase breakdown + boundedness verdict
    from the time-attribution plane (``with_timeline=False`` opts
    out), and the static verifier's latest findings for the program
    with error sites marked inline (``with_lint=False`` opts out)."""
    lines = []
    if with_compile_report:
        lines.extend(_compile_report_lines(program))
    if with_timeline:
        lines.extend(_time_attribution_lines())
    marks = {}
    if with_lint:
        header, marks = _lint_lines(program)
        lines.extend(header)
    if with_numerics:
        header, nmarks = _numerics_lines(program)
        lines.extend(header)
        for k, v in nmarks.items():
            marks.setdefault(k, v)
    for block in program.blocks:
        lines.append(f"block {block.idx}:")
        for name, var in sorted(block.vars.items()):
            shape = f" shape={list(var.shape)}" if (
                with_shapes and var.shape is not None) else ""
            tags = "".join(
                t for t, on in ((" param", var.is_parameter),
                                (" persistable", var.persistable),
                                (" stop_grad", var.stop_gradient)) if on
            )
            lines.append(f"  var {name}{shape}{tags}")
        for i, op in enumerate(block.ops):
            ins = ", ".join(
                f"{k}={v}" for k, v in op.inputs.items() if v)
            outs = ", ".join(
                f"{k}={v}" for k, v in op.outputs.items() if v)
            mark = marks.get(i, "") if block.idx == 0 else ""
            lines.append(f"  [{i}] {op.type}({ins}) -> {outs}{mark}")
    return "\n".join(lines)


def draw_block_graphviz(program: Program, block_idx: int = 0,
                        path: Optional[str] = None,
                        highlights: Optional[set] = None) -> str:
    """Graphviz dot source for one block's dataflow: op nodes (boxes)
    connected through var nodes (ellipses). Write to ``path`` if given."""
    block = program.blocks[block_idx]
    highlights = highlights or set()
    lines = ["digraph G {", "  rankdir=TB;"]
    # sequential ids: deterministic across runs and collision-free
    var_ids: dict = {}

    def var_node(name):
        if name not in var_ids:
            var_ids[name] = f"var_{len(var_ids)}"
            color = ' style=filled fillcolor="#ffd27f"' \
                if name in highlights else ""
            lines.append(
                f'  {var_ids[name]} [label="{name}" shape=ellipse{color}];')
        return var_ids[name]

    for i, op in enumerate(block.ops):
        op_id = f"op_{i}"
        lines.append(
            f'  {op_id} [label="{op.type}" shape=box '
            f'style=filled fillcolor="#cfe2ff"];'
        )
        for n in op.input_arg_names:
            if n:
                lines.append(f"  {var_node(n)} -> {op_id};")
        for n in op.output_arg_names:
            if n:
                lines.append(f"  {op_id} -> {var_node(n)};")
    lines.append("}")
    dot = "\n".join(lines)
    if path:
        with open(path, "w") as f:
            f.write(dot)
    return dot
