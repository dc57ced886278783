"""Stage time: a traced window's device time put on the program's stages.

The program wraps each stage of its step in ``jax.named_scope(<stage>)``
(``repro/obs/stages.py``: ``fft.x``, ``fold.exchange``,
``relayout.permute``, ``pointwise.cross``, ...), so the compiled HLO gives
every instruction an ``op_name`` that names its stage. This module puts
each device op of a trace on one stage, beside the op classes of
``bench/tracing.py`` (which it uses and leaves as they are):

* the stage tables ``bench/stages/<layer>/*.json`` are regular expressions
  on ``op_name``; an op's stage is the **innermost** scope that a table
  matches (``jit(step)/pointwise.cross/relayout.permute/transpose`` is
  ``relayout.permute``);
* an op with none takes the stage of its producer: its first operand in
  the compiled HLO is followed through ops that have no stage (XLA's
  ``copy``, ``copy-start``/``copy-done``, ``bitcast``) until one has;
* a walk that ends at a program parameter is ``relayout.entry``, XLA's
  layout copy of an argument; what is left is ``unstaged``;
* a stage's time on a device is the part of the union of its class's op
  intervals that its ops cover first (ops sorted by start), so the stages
  of a class partition that class's time exactly.

Times are means over the chips, in seconds over the whole window, like
``tracing.Summary``; :func:`per_step_ms` divides by the steps.

As a script it traces one cell of ``BENCHMARK.json`` and prints the stage
breakdown, on whatever accelerator JAX finds::

    python3 bench/stagetime.py --workload tgv_re1600.n256.1x1 --seed 7

``--n`` overrides the grid size, ``--record DIR`` keeps the trace and the
programs' scopes as a test fixture, and ``--obs-seconds S`` also times the
closed loop for ``S`` seconds with ``repro.obs`` off and on.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import importlib
import json
import os
import re
import shutil
import sys
import tempfile
from pathlib import Path

if __package__ in (None, ""):          # run as a script: find the checkout
    _ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import tracing  # noqa: E402

STAGES = Path(__file__).resolve().parent / "stages"
ENTRY = "relayout.entry"
UNSTAGED = "unstaged"

_INSTR = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")


# ---------------------------------------------------------------------------
# the compiled program: each instruction's opcode, scope and operands
# ---------------------------------------------------------------------------

def instructions(hlo_text: str):
    """``(computation, name, text)`` of every instruction of an HLO module,
    with the lines an instruction runs over joined (a Pallas kernel's
    ``kernel_metadata`` breaks its line, and its ``op_name`` follows)."""
    comp, cur = None, None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        head = _COMPUTATION.match(line)
        m = None if head else _INSTR.match(line)
        if cur is not None and (head or m or line == "}"):
            yield tuple(cur)
            cur = None
        if head:
            comp = head.group(2)
        elif m:
            cur = [comp, m.group(1), m.group(2)]
        elif cur is not None:
            cur[2] += "\n" + line
    if cur is not None:
        yield tuple(cur)


def hlo_graph(hlo_text: str) -> dict:
    """``{instruction: [opcode, op_name, operand, ...]}`` of the
    instructions that run as ops: those of every computation that is not
    the body of a fusion or the reducer of a reduce (their instructions
    never appear in a trace)."""
    called = set(_CALLED.findall(hlo_text))
    out = {}
    for comp, name, text in instructions(hlo_text):
        op = _OPCODE.search(text)
        if comp in called or not op:
            continue
        scope = _OP_NAME.search(text)
        out[name] = ([op.group(1), scope.group(1) if scope else ""]
                     + _OPERAND.findall(_args(text, op.end())))
    return out


def _args(text: str, start: int) -> str:
    """The text between the parenthesis opened before ``start`` and the
    one that closes it."""
    depth = 1
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return text[start:i]
    return text[start:]


# ---------------------------------------------------------------------------
# stages
# ---------------------------------------------------------------------------

def load_stage_tables(root: Path = STAGES) -> list:
    """``[(layer, compiled regex)]`` from every ``root/<layer>/*.json``."""
    tables = []
    for layer_dir in sorted(p for p in root.iterdir() if p.is_dir()):
        for f in sorted(layer_dir.glob("*.json")):
            for m in json.loads(f.read_text())["match"]:
                tables.append((layer_dir.name, re.compile(m["regex"])))
    return tables


def innermost(op_name: str, tables) -> str | None:
    """The innermost stage scope of ``op_name``, or None."""
    best, at = None, -1
    for _layer, rx in tables:
        for m in rx.finditer(op_name):
            start = m.start() + (op_name[m.start()] == "/")
            if start > at:
                end = len(op_name)
                for stop in "/)":
                    j = op_name.find(stop, start)
                    if j >= 0:
                        end = min(end, j)
                best, at = op_name[start:end], start
    return best


def resolve(instr: str, graph: dict, tables) -> str:
    """The stage of one instruction of a program whose
    :func:`hlo_graph` is ``graph``."""
    seen = set()
    while instr in graph and instr not in seen:
        seen.add(instr)
        opcode, op_name, *operands = graph[instr]
        stage = innermost(op_name, tables)
        if stage:
            return stage
        if opcode == "parameter":
            return ENTRY
        if not operands:
            break
        instr = operands[0]
    return UNSTAGED


@dataclasses.dataclass
class StageTimes:
    """Per-chip means over the traced window, seconds."""
    steps: int
    local_s: dict          # stage -> seconds of ``local``-class ops
    all_s: dict            # stage -> seconds of every (non-async) op
    class_s: dict          # class -> seconds, as ``tracing.reduce`` has it
    other_ops: dict        # "module:instr [opcode] stage" -> seconds of the
                           # local ops on neither a relayout nor a
                           # pointwise stage


def reduce(cap: tracing.Capture, device_ids, steps: int, class_tables: dict,
           scopes: dict, graphs: dict, stage_tables) -> StageTimes:
    """Stage times of one traced window. ``scopes`` is what
    ``tracing.reduce`` takes (``{module: tracing.op_names(...)}``, for the
    op classes) and ``graphs`` is ``{module: hlo_graph(...)}``."""
    ids = list(device_ids)
    chips = len(ids)
    classes = tracing.reduce(cap, ids, steps, class_tables, scopes,
                             (0.0, 0.0), {}).class_s
    local_s, all_s, other, cache = {}, {}, {}, {}

    def stage_of(op):
        key = (op.module, op.instr)
        if key not in cache:
            cache[key] = resolve(op.instr, graphs.get(op.module, {}),
                                 stage_tables)
        return cache[key]

    def add(out, key, ns):
        out[key] = out.get(key, 0.0) + ns * 1e-9 / chips

    for d in ids:
        ops = [op for op in cap.devices[d] if not op.is_async]
        for op, ns in _partition(ops):
            add(all_s, stage_of(op), ns)
        local = [op for op in ops
                 if tracing.classify(op, class_tables, scopes) == "local"]
        for op, ns in _partition(local):
            stage = stage_of(op)
            add(local_s, stage, ns)
            if stage.split(".", 1)[0] not in ("relayout", "pointwise"):
                node = graphs.get(op.module, {}).get(op.instr, ["?"])
                add(other, f"{op.module}:{op.instr} [{node[0]}] {stage}", ns)
    return StageTimes(steps=steps, local_s=local_s, all_s=all_s,
                      class_s=classes, other_ops=other)


def _partition(ops):
    """``[(op, ns)]``: each op's share of the union of the ops' intervals,
    the part no op that starts earlier already covers."""
    covered = float("-inf")
    for op in sorted(ops, key=lambda o: (o.start, o.end)):
        share = max(0.0, op.end - max(op.start, covered))
        covered = max(covered, op.end)
        yield op, share


def per_step_ms(seconds: float, steps: int) -> float:
    return 1e3 * seconds / steps


def layer_ms(t: StageTimes, layer: str) -> float | None:
    """Device time per step of the ``local`` ops of one layer's stages
    (``relayout`` gives ``relayout_ms``, ``pointwise`` ``pointwise_ms``);
    None where it is zero."""
    s = sum(v for k, v in t.local_s.items() if k.split(".", 1)[0] == layer)
    return per_step_ms(s, t.steps) if s > 0 else None


def breakdown(t: StageTimes) -> list:
    """``[[stage, ms per step]]`` over every op, longest first."""
    return [[k, per_step_ms(v, t.steps)]
            for k, v in sorted(t.all_s.items(), key=lambda kv: -kv[1])]


def summary(t: StageTimes, top: int = 20) -> dict:
    """What the script prints for one traced window."""
    local = t.class_s.get("local", 0.0)
    unstaged = t.local_s.get(UNSTAGED, 0.0)
    ops = sorted(t.other_ops.items(), key=lambda kv: -kv[1])[:top]
    return {
        "steps": t.steps,
        "class_ms": {c: per_step_ms(v, t.steps) for c, v in t.class_s.items()},
        "relayout_ms": layer_ms(t, "relayout"),
        "pointwise_ms": layer_ms(t, "pointwise"),
        "unstaged_ms": per_step_ms(unstaged, t.steps),
        "unstaged_share_of_local": unstaged / local if local else None,
        "local_stages": [[k, per_step_ms(v, t.steps)] for k, v in sorted(
            t.local_s.items(), key=lambda kv: -kv[1])],
        "stages": breakdown(t),
        "other_local_ops": [[k, per_step_ms(v, t.steps)] for k, v in ops],
    }


# ---------------------------------------------------------------------------
# the script: one traced window of a cell, on the chip
# ---------------------------------------------------------------------------

def _trace(fn, keep: str | None):
    """``(fn(), Capture, xplane path or None)`` like ``tracing.capture``,
    keeping the ``.xplane.pb`` in ``keep`` when asked."""
    import jax

    tmp = tempfile.mkdtemp(prefix="bench_stages_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        cap = tracing.load(path)
        kept = None
        if keep is not None:
            kept = f"{keep}.xplane.pb"
            shutil.copy(path, kept)
        return out, cap, kept
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _obs_cost(case, seconds: float, in_flight: int) -> dict:
    """``step_ms`` of the closed loop with ``repro.obs`` off and on,
    alternating off/on/off/on so that drift cancels."""
    from bench import harness
    from repro import obs

    runs = {"off": [], "on": []}
    for state in ("off", "on", "off", "on"):
        (obs.enable if state == "on" else obs.disable)()
        try:
            steps, window = harness.closed_loop(case, seconds=seconds,
                                                in_flight=in_flight)
        finally:
            obs.disable()
        runs[state].append(1e3 * window / steps)
    obs.clear()
    return runs


def main(argv=None) -> int:
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--n", type=int, default=0,
                    help="grid size in place of the traffic's")
    ap.add_argument("--steps", type=int, default=0,
                    help="traced steps in place of the traffic's")
    ap.add_argument("--record", default="",
                    help="path stem: keep <stem>.xplane.pb and "
                         "<stem>.scopes.json")
    ap.add_argument("--obs-seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    import jax

    spec = harness.load_cell(args.workload)
    cell, config, traffic = spec["cell"], spec["config"], dict(spec["traffic"])
    if args.n:
        traffic["n"] = args.n
        config = {k: v for k, v in config.items() if k != "n"}
    steps = args.steps or int(traffic["trace_steps"])
    devices = harness.devices_for(cell["chips"], require_tpu=False)
    if devices[0].platform == "tpu":
        harness.use_compile_cache()
    # JAX's persistent cache leaves metadata out of its key, so it would
    # hand back a program compiled from another commit (the same HLO
    # without the stage scopes) with that commit's op_names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    mesh = harness.make_mesh(traffic["mesh"], devices)
    used = list(mesh.devices.flat)
    kind = importlib.import_module(f"bench.kinds.{config['kind']}")
    case = kind.Case(config, traffic, mesh, args.seed)
    case.setup()
    in_flight = int(traffic["in_flight"])

    def window():
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            return harness.closed_loop(case, steps=steps, in_flight=in_flight,
                                       spans=True)

    keep = args.record or None
    _, cap, kept = _trace(window, keep)
    scopes, graphs = {}, {}
    for fn, fargs in case.programs():
        text = fn.lower(*fargs).compile().as_text()
        module = tracing.module_name(text)
        graphs[module] = hlo_graph(text)
        scopes[module] = {k: v for k, v in tracing.op_names(text).items()
                          if k in graphs[module]}
    t = reduce(cap, [d.id for d in used], steps, tracing.load_tables(),
               scopes, graphs, load_stage_tables())
    out = {"workload": args.workload, "n": traffic["n"],
           "device": devices[0].device_kind, **summary(t)}
    if keep is not None:
        Path(f"{keep}.scopes.json").write_text(json.dumps(
            {"op_name": scopes, "graph": graphs}, sort_keys=True))
        out["recorded"] = [kept, f"{keep}.scopes.json"]
    if args.obs_seconds:
        out["obs_step_ms"] = _obs_cost(case, args.obs_seconds, in_flight)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
