"""The program's own spans and named scopes in a profiler trace.

``tracing.load`` keeps the harness's spans (``bench.*``) and the device's
ops by name.  ``load`` here adds what the program writes itself
(``repro.trace``):

  host     also the program's host spans (names starting with one of
           ``repro.trace.PREFIXES``), in the same [name, start_ns, dur_ns,
           thread] form, so ``tracing.reduce`` labels each idle gap with
           the innermost span open in it, harness or program;
  scoped   per device plane, [scope, start_ns, dur_ns] for each op whose
           named-scope path holds a program scope.  ``scope`` joins those
           scopes with ``/``, outermost first (``trueknn.fused/
           trueknn.round.b2``).  On a TPU v5e an op event carries no
           scope: its stats are only ``device_offset_ps``,
           ``device_duration_ps`` and ``Time Scale Multiplier``, and
           neither ``tf_op`` nor ``long_name`` is there.  The path is the
           ``op_name`` metadata of the op's HLO instruction, read from the
           HLO protos that a capture with ``enable_hlo_proto`` keeps in
           the trace (``module_scopes``), in the module run that holds the
           op.

``reduce`` turns that into two tables:

  spans    per span name: count, seconds and self seconds (its time less
           that of the spans nested directly inside it on its thread);
  scopes   per scope and each of its dotted prefixes (``trueknn``,
           ``trueknn.round``, ``trueknn.round.b2``): device seconds, the
           union of its ops' intervals, so a ``while`` op and the ops of
           its body count once.

Both read nothing but the record, so a recorded trace checks them on any
machine.
"""

from __future__ import annotations

import bisect

from repro.trace import PREFIXES

from benchmarks.chip import tracing

METADATA_PLANE = "/host:metadata"


def _program_scope(path: str) -> str:
    """``jit(run)/trueknn.fused/while/body/trueknn.round.b2/add`` ->
    ``trueknn.fused/trueknn.round.b2``: the program's scopes in a path."""
    return "/".join(p for p in path.split("/") if p.startswith(PREFIXES))


def _varint(buf: bytes, i: int):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf: bytes):
    """(number, value) of each varint or length-delimited field of one
    protobuf message, in order; fixed-width fields are skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"unexpected protobuf wire type {kind}")
        yield key >> 3, val


def _sub(buf: bytes, number: int):
    """Every length-delimited field ``number`` of a message."""
    return [v for f, v in _fields(buf) if f == number]


def module_scopes(xspace: bytes) -> dict:
    """{module event name: {HLO instruction: program scope}} from the HLO
    protos that a capture with ``enable_hlo_proto`` keeps in its
    ``/host:metadata`` plane.  The walk follows the protobuf field numbers
    of XSpace.planes (1), XPlane.name (2) and .event_metadata (4, a map
    whose value is 2), XEventMetadata.name (2) and .stats (5),
    XStat.bytes_value (6), HloProto.hlo_module (1),
    HloModuleProto.computations (3), HloComputationProto.instructions (2),
    HloInstructionProto.name (1) and .metadata (7), OpMetadata.op_name
    (2)."""
    out = {}
    for plane in _sub(xspace, 1):
        if _sub(plane, 2) != [METADATA_PLANE.encode()]:
            continue
        for entry in _sub(plane, 4):
            for md in _sub(entry, 2):
                name = b"".join(_sub(md, 2)).decode()
                scopes = out.setdefault(name, {})
                for stat in _sub(md, 5):
                    for proto in _sub(stat, 6):
                        for module in _sub(proto, 1):
                            _instruction_scopes(module, scopes)
    return {name: sc for name, sc in out.items() if sc}


def _instruction_scopes(module: bytes, scopes: dict) -> None:
    for comp in _sub(module, 3):
        for instr in _sub(comp, 2):
            name = b"".join(_sub(instr, 1)).decode()
            for meta in _sub(instr, 7):
                scope = _program_scope(b"".join(_sub(meta, 2)).decode())
                if scope:
                    scopes[name] = scope


def load(path: str) -> dict:
    """``tracing.load(path)`` with the program's spans and scopes added.
    Scopes need a capture made with ``ProfileOptions.enable_hlo_proto``;
    without it no op has one."""
    from jax.profiler import ProfileData

    record = tracing.load(path)
    wlen = record["window_ns"]
    with open(path, "rb") as f:
        xspace = f.read()
    by_module = module_scopes(xspace)
    host, scoped = [], {}
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIXES + (tracing.WINDOW_SPAN,)):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns), line.name])
        elif plane.name in record["devices"]:
            lines = {line.name: list(line.events) for line in plane.lines}
            # each op belongs to the module run that holds its start
            runs = sorted((int(ev.start_ns), ev.name)
                          for ev in lines.get(tracing.MODULES_LINE, []))
            starts = [s for s, _ in runs]
            out = scoped.setdefault(plane.name, [])
            for ev in lines.get(tracing.OPS_LINE, []):
                at = bisect.bisect_right(starts, int(ev.start_ns)) - 1
                scopes = by_module.get(runs[at][1], {}) if at >= 0 else {}
                scope = scopes.get(tracing.op_name(ev.name))
                if scope:
                    out.append([scope, int(ev.start_ns),
                                int(ev.duration_ns)])
    # the window tracing.load clipped to: the first one in the file
    w0 = next(s for n, s, _, _ in host if n == tracing.WINDOW_SPAN)
    record["host"] += [[n, s - w0, d, t] for n, s, d, t in host
                       if n != tracing.WINDOW_SPAN
                       and s - w0 < wlen and s + d - w0 > 0]
    for name, ops in scoped.items():
        clipped = []
        for scope, s, d in ops:
            a, b = max(s - w0, 0), min(s + d - w0, wlen)
            if b > a:
                clipped.append([scope, a, b - a])
        record["devices"][name]["scoped"] = clipped
    return record


def span_table(host) -> dict:
    """{name: {"count", "seconds", "self_seconds"}} of host spans
    [name, start_ns, dur_ns, thread]; a span's children are the spans that
    nest directly inside it on its thread."""
    table, by_thread = {}, {}
    for ev in host:
        by_thread.setdefault(ev[3], []).append(ev)
    for evs in by_thread.values():
        stack = []  # [name, end, self_ns] of the open spans
        # parents before their children: earlier start, then longer first
        for name, s, d, _ in sorted(evs, key=lambda e: (e[1], -e[2])):
            while stack and stack[-1][1] <= s:
                _close(table, stack.pop())
            if stack:
                stack[-1][2] -= d
            stack.append([name, s + d, d])
            row = table.setdefault(name, {"count": 0, "seconds": 0.0,
                                          "self_seconds": 0.0})
            row["count"] += 1
            row["seconds"] += d / 1e9
        while stack:
            _close(table, stack.pop())
    return table


def _close(table, entry) -> None:
    name, _, self_ns = entry
    table[name]["self_seconds"] += self_ns / 1e9


def _prefixes(scope: str):
    """Every scope in ``scope`` and each of its dotted prefixes."""
    keys = set()
    for part in scope.split("/"):
        bits = part.split(".")
        keys.update(".".join(bits[:i]) for i in range(1, len(bits) + 1))
    return keys


def scope_table(record: dict) -> dict:
    """{scope: device seconds}, each the union of its ops' intervals,
    averaged over the device planes that ran a scoped op."""
    totals, n_dev = {}, 0
    for _, dev in sorted(record["devices"].items()):
        if not dev.get("scoped"):
            continue
        n_dev += 1
        intervals = {}
        for scope, s, d in dev["scoped"]:
            for key in _prefixes(scope):
                intervals.setdefault(key, []).append((s, s + d))
        for key, ivs in intervals.items():
            busy = sum(e - s for s, e in tracing._union(ivs))
            totals[key] = totals.get(key, 0) + busy
    return {k: v / 1e9 / max(n_dev, 1) for k, v in sorted(totals.items())}


def reduce(record: dict) -> dict:
    """``{"spans": span_table, "scopes": scope_table}`` of a record."""
    return {"spans": span_table(record["host"]),
            "scopes": scope_table(record)}
