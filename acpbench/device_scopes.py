"""The device's time by the program's own scopes.

The programs open `jax.named_scope("acp.<layer>")` around what each op is
for (`agentcontrolplane_tpu/observability/scopes.py`), and finer plain
scopes inside (`attn_qkv`, `page_walk`, `moe_route`, `ssm_update`, ...). A
scope is a component of the op's name-stack path, which the profiler writes
into the `.xplane.pb` as the stat `tf_op` of the op's EVENT METADATA, beside
`source` (file:line), `hlo_category` and `program_id`.
`jax.profiler.ProfileData` hands out an event's own stats and not its
metadata's, so `op_table` reads the protobuf wire format itself (standard
library only; "with nothing but JAX" holds). The join to `trace_reduce`'s op
intervals is by (`program_id`, event name): the event name is
`ProfileData`'s `e.name` letter for letter, the program the number in the
name of the module run the op lies in (`jit_decode_block(9617...)`).

    op_table(path) -> {plane: {(program_id, event name): Op(tf_op, source, category)}}
    attribute(op_intervals, runs, tables) -> seconds by (phase, top level), (phase, leaf), ...
    analyse(run) -> all of it for one traced run, printed once as a `[scopes]` line

Every op second of the reduced trace lands in exactly one top level of one
phase, so the parts sum to `trace_reduce`'s `ops`. A program without the
scopes (a parent commit, or an executable compiled before them and served
from a compile cache, whose key strips locations) gives `analyse(run) is
None`, and every metric read from here is left out. Where XLA fuses the tail
of one scope with the head of the next (a residual add with the next norm's
reduction) the fusion carries one op's name and its time lands there.

The vocabulary is the harness's own copy: a program PR that renames a scope
cannot move the yardstick without a test saying so
(`tests/acpbench/test_device_scopes.py`).
"""

from __future__ import annotations

import bisect
import json
import re
import time
from typing import NamedTuple

from . import host_spans, metrics, spec, trace_reduce
from .layer_metrics._common import DECODE, PREFILL, decode_steps_traced, traced_window

PREFIX = "acp."
TOP_LEVELS = ("embed", "attn", "mixer", "ffn", "commit", "head", "sample")
UNNAMED = "unnamed"
LEAVES = (
    "attn_qkv", "page_walk", "window_walk", "decode_attention", "prefill_attention", "full_gather", "attn_out",
    "short_conv", "conv_in_proj", "conv_out_proj",
    "mamba_in_proj", "mamba_conv", "mamba_x_proj", "ssm_scan", "ssm_update", "mamba_out_proj",
    "ffn_dense", "moe_route", "moe_sort", "moe_gmm", "moe_combine", "window_commit",
)
# a Pallas kernel is named by the program (`name=` of its `pallas_call`): where its event carries no path, its name files it
KERNELS = {
    "paged_page_walk": ("attn", "page_walk"), "paged_window_walk": ("attn", "window_walk"),
    "moe_gmm": ("ffn", "moe_gmm"), "ssm_update": ("mixer", "ssm_update"), "ssm_scan": ("mixer", "ssm_scan"),
}
KERNEL = re.compile(r"^%?(" + "|".join(KERNELS) + r")[.\d]* = ")
MATMUL = "convolution fusion"  # the TPU compiler's category for a fusion around a dot
GLUE_LEVELS = ("attn", "mixer", "ffn")
TOP = 8


class Op(NamedTuple):
    tf_op: str  # jit(decode_block)/while/body/closed_call/acp.attn/attn_qkv/dot_general: ("" = none recorded)
    source: str  # agentcontrolplane_tpu/models/llama.py:428
    category: str  # hlo_category: "convolution fusion", "loop fusion", "data formatting", "custom-call", ...


# -- the protobuf wire format, as far as an XSpace needs it ---------------------------------------------
# XSpace {1: planes}; XPlane {2: name, 3: lines, 4: event_metadata map, 5: stat_metadata map};
# map entry {1: key, 2: value}; XEventMetadata {2: name, 5: stats}; XStatMetadata {2: name};
# XStat {1: metadata_id, 3: uint64, 4: int64, 5: str, 7: ref (a stat_metadata id whose name is the value)}

def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf, at: int, end: int):
    """(field number, value) of one message: a varint's value, or (start,
    end) of a length-delimited field, which is skipped unread."""
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = (at, at + size), at + size
        elif wire in (1, 5):
            value, at = None, at + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {at}: not an XSpace")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, entries):
    for span in entries:
        got = dict(_fields(buf, *span))
        if 1 in got and 2 in got:
            yield got[1], got[2]


def op_table(path: str) -> dict[str, dict[tuple[int, str], Op]]:
    """For each device plane, what the profiler recorded about each XLA op
    beside its events: {(program_id, event name): Op}. The lines, which hold
    the events and most of the file, are skipped whole."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events, stats = "", [], []
        for field, value in _fields(buf, *plane):
            if field == 2:
                name = _text(buf, value)
            elif field == 4:
                events.append(value)
            elif field == 5:
                stats.append(value)
        if not name.startswith("/device:TPU:"):
            continue
        stat_name = {key: _text(buf, dict(_fields(buf, *span)).get(2, (0, 0))) for key, span in _map_values(buf, stats)}
        table = out[name] = {}
        for _, span in _map_values(buf, events):
            event, found = "", {}
            for field, value in _fields(buf, *span):
                if field == 2:
                    event = _text(buf, value)
                elif field == 5:
                    stat = dict(_fields(buf, *value))
                    kind = stat_name.get(stat.get(1))
                    if kind in ("tf_op", "source", "hlo_category"):
                        found[kind] = _text(buf, stat[5]) if 5 in stat else stat_name.get(stat.get(7), "")
                    elif kind == "program_id":
                        found[kind] = stat.get(3, stat.get(4))
            if "program_id" in found:
                table[(found["program_id"], event)] = Op(
                    found.get("tf_op", ""), found.get("source", ""), found.get("hlo_category", ""))
    return out


# -- what an op is for ------------------------------------------------------------------------------------

def top_level(tf_op: str) -> str | None:
    """The innermost `acp.<layer>` of the path (of its first path that has
    one: a merged instruction carries several, `;` between them)."""
    for path in tf_op.split(";"):
        found = [part[len(PREFIX):] for part in path.split("/") if part.startswith(PREFIX)]
        if found:
            return found[-1]
    return None


def leaf(tf_op: str) -> str | None:
    """The last component of the path that is of the leaf vocabulary."""
    for path in tf_op.split(";"):
        found = [part for part in path.split("/") if part in LEAVES]
        if found:
            return found[-1]
    return None


def classify(event: str, op: Op | None) -> tuple[str, str | None, bool]:
    """(top level or `unnamed`, leaf, whether the op is a Pallas kernel)."""
    kernel = KERNEL.match(event)
    top = top_level(op.tf_op) if op else None
    if top not in TOP_LEVELS:
        top = None
    if kernel and top is None:
        return (*KERNELS[kernel.group(1)], True)
    low = leaf(op.tf_op) if op else None
    if kernel and low is None:
        low = KERNELS[kernel.group(1)][1]
    return top or UNNAMED, low, bool(kernel)


def module_runs(profile) -> list[tuple[str, list[tuple[int, int, str, int]]]]:
    """(plane, [(start, end, module, program id) of every program run, by
    start]) for the device planes `trace_reduce` keeps, in its order."""
    out = []
    for plane in trace_reduce.device_planes(profile):
        ops, mods = (trace_reduce._line(plane, name) for name in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE))
        runs = []
        for e in (mods.events if mods is not None else ()):
            m = re.search(r"\((\d+)\)$", e.name.strip())
            runs.append((int(e.start_ns), int(e.start_ns + e.duration_ns), trace_reduce.module_name(e.name),
                         int(m.group(1)) if m else -1))
        if ops is not None and len(runs) >= 3 and any(True for _ in ops.events):  # `reduce_profile`'s own conditions
            out.append((plane.name, sorted(runs)))
    return out


def phase_of(module: str) -> str:
    return "decode" if re.search(DECODE, module) else "prefill" if re.search(PREFILL, module) else "other"


def attribute(op_intervals, runs, tables) -> dict:
    """Every op interval given the program whose run it lies in, and by it
    its scope, leaf, category and source; seconds summed as `trace_reduce`
    sums `ops` (mean over chips), decode-block runs, prefill and
    continuation runs and the rest apart.

    `op_intervals`: per chip [(start, end, event name)]; `runs`: per chip
    (plane name, [(start, end, module, program id)]); `tables`: `op_table`'s.
    An op outside every run, or that the table does not know, is `unnamed`."""
    n = max(1, len(op_intervals))
    sums = {key: {} for key in ("top", "leaf", "glue", "kernel", "unnamed_by_category", "ops")}
    scoped, seen, modules = set(), set(), {}

    def add(table, key, ns):
        table[key] = table.get(key, 0.0) + ns / 1e9 / n

    for ops, (plane, chip_runs) in zip(op_intervals, runs):
        # nanoseconds by (program, event) first: a slice holds a few thousand distinct ops and a million intervals
        spent: dict[tuple[int, str], int] = {}
        starts = [r[0] for r in chip_runs]
        for start, end, event in ops:
            at = bisect.bisect_right(starts, start) - 1
            program = chip_runs[at][3] if at >= 0 and start < chip_runs[at][1] else -1
            key = (program, event)
            spent[key] = spent.get(key, 0) + end - start
        modules.update({r[3]: r[2] for r in chip_runs})
        table = tables.get(plane, {})
        for (program, event), ns in spent.items():
            op = table.get((program, event))
            seen.add(program)
            phase = phase_of(modules[program]) if program in modules else "other"
            top, low, kernel = classify(event, op)
            if op and PREFIX in op.tf_op:
                scoped.add(program)
            add(sums["top"], (phase, top), ns)
            if low:
                add(sums["leaf"], (phase, low), ns)
            if kernel:
                add(sums["kernel"], (phase, KERNEL.match(event).group(1)), ns)
            category = op.category if op else "not in the table"
            if top == UNNAMED:
                add(sums["unnamed_by_category"], (phase, category), ns)
            if phase == "decode" and not kernel and category != MATMUL:
                if top in GLUE_LEVELS:
                    add(sums["glue"], "decode", ns)
                where = (op.source or "/".join(op.tf_op.rstrip(":").split("/")[-2:])) if op else ""
                add(sums["ops"], (trace_reduce.op_name(event), f"{top}/{low}" if low else top, where), ns)
    served = {modules[p] for p in seen if p in modules and phase_of(modules[p]) != "other"}
    named = {modules[p] for p in scoped if p in modules}
    sums["programs"] = {"scoped": sorted(named), "unscoped": sorted(served - named)}
    return sums


def analyse(run) -> dict | None:
    """Once a run: the attribution, kept on the run for the other readers,
    and its `[scopes]` line. None where there is no trace, or where no
    decode block of the trace was compiled with the scopes."""
    if run.trace is None:
        return None
    if not hasattr(run, "device_scopes"):
        t0 = time.monotonic()
        path = host_spans.find(run)
        found = None
        if path:
            import jax

            found = attribute(run.trace["op_intervals"], module_runs(jax.profiler.ProfileData.from_file(path)),
                              op_table(path))
            found["steps"] = decode_steps_traced(run)
            found["prompt_tokens"] = prompt_tokens(run)
            found["reader_s"] = time.monotonic() - t0
        print(f"[scopes] {line(found, run.trace)}", flush=True)
        scoped = found is not None and any(re.search(DECODE, m) for m in found["programs"]["scoped"])
        run.device_scopes = found if scoped else None
    return run.device_scopes


def prompt_tokens(run) -> int | None:
    """Prompt tokens whose first token came inside the traced window, as
    `prefill_ms_per_ktok` counts them."""
    window = traced_window(run)
    if window is None:
        return None
    return sum(r.prompt_len for r in run.records if metrics.in_window(r.first_t, window))


def _by(table: dict, phase: str, scale: float) -> dict:
    return {key[1]: round(s * scale, 4) for key, s in sorted(table.items()) if key[0] == phase}


def line(found: dict | None, reduced: dict) -> str:
    if found is None:
        return "no trace file found: nothing read"
    steps, tokens = found["steps"] or 0, found["prompt_tokens"]
    unscoped = found["programs"]["unscoped"]
    stale = ("" if not unscoped else
             f"; NO acp.* scope in any op of {unscoped}: compiled before the scopes (a parent commit, or an "
             f"executable served from a compile cache an older tree filled: jax's cache key strips locations, "
             f"so clear the cache once)")
    if not steps:
        return f"no whole decode block in the slice{stale}; reader {found['reader_s']:.2f} s"
    per_step = 1e3 / steps
    tops = _by(found["top"], "decode", per_step)
    total = sum(s for (phase, _), s in found["top"].items() if phase == "decode") * per_step
    module = trace_reduce.seconds_of(reduced, "modules", DECODE) * per_step
    pre = "none in the slice" if not tokens else json.dumps(
        _by(found["top"], "prefill", 1e6 / tokens))
    glue = sorted(found["ops"].items(), key=lambda kv: -kv[1])[:TOP]
    glue = [[name, scope, where.replace(spec.ROOT + "/", ""), round(s * per_step, 4)] for (name, scope, where), s in glue]
    unnamed = sorted(((k, s) for k, s in found["unnamed_by_category"].items()), key=lambda kv: -kv[1])[:TOP]
    unnamed = [[f"{phase}: {category}", round(s * 1e3, 3)] for (phase, category), s in unnamed]
    kernels = {}  # over every run, to hold against `trace_reduce`'s seconds of the kernel's name
    for (_, name), s in found["kernel"].items():
        kernels[name] = kernels.get(name, 0.0) + s
    same = all(abs(s - trace_reduce.seconds_of(reduced, "ops", "^" + name)) <= 1e-9 * s for name, s in kernels.items())
    return (f"decode ms a step by scope {json.dumps(tops)} sum {total:.4f} (module span {module:.4f}: it holds "
            f"in-module waits) over {steps:g} steps; by leaf {json.dumps(_by(found['leaf'], 'decode', per_step))}; "
            f"kernels {json.dumps(_by(found['kernel'], 'decode', per_step))} (each kernel's seconds over every run "
            f"equal trace_reduce's: {same}); glue {found['glue'].get('decode', 0.0) * per_step:.4f}; "
            f"prefill ms a 1,000 prompt tokens by scope {pre}; largest decode ops that are neither kernel nor "
            f"matmul [op, scope, source (or the path's end), ms a step] {json.dumps(glue)}; largest unnamed by category, ms of the slice "
            f"{json.dumps(unnamed)}; named {named_share(found):.2f}% of the op seconds{stale}; "
            f"reader {found['reader_s']:.2f} s")


# -- the metrics ----------------------------------------------------------------------------------------------

def step_ms(run, *tops: str) -> float | None:
    """Device ms a decode step under the given top levels, in decode-block runs."""
    found = analyse(run)
    if found is None or not found["steps"]:
        return None
    return sum(found["top"].get(("decode", top), 0.0) for top in tops) * 1e3 / found["steps"]


def glue_ms_per_step(run) -> float | None:
    found = analyse(run)
    if found is None or not found["steps"]:
        return None
    return found["glue"].get("decode", 0.0) * 1e3 / found["steps"]


def named_share(found: dict) -> float:
    total = sum(found["top"].values())
    return 100.0 * sum(s for (_, top), s in found["top"].items() if top != UNNAMED) / total if total else 0.0


def device_named_share(run) -> float | None:
    found = analyse(run)
    return None if found is None else named_share(found)
