"""Device time under the exchange's named scopes, from a traced run.

The fused exchange puts its device ops under three `jax.named_scope`s:
`exchange.encode`, `exchange.gather` and `exchange.decode`. The compiler
keeps the name stack as each instruction's `op_name` metadata (a fusion
carries its root's). A TPU op event of the profiler names only its HLO
instruction, so the scope comes from the optimised HLO that the profiler
stores, per module, in its `/host:metadata` plane ("Hlo Proto"). Steps, so
that the last two can be checked on a small recorded trace:

* `hlo_scopes(xspace)` reads those HLO protos: per module, the scope of
  each instruction that has one;
* `extract(path)` reads the `.xplane.pb`: each TPU's op and module events
  and the `bench.window` host span (`tracereduce.extract`), and
  `hlo_scopes`;
* `assign(device, scopes_by_module)` gives each op the scope of its
  instruction in the module running it, as ``[scope, start_ns, end_ns]``;
* `seconds(events)` sums the op time per scope and chip inside the window.

`device_seconds(rec, scope)` is what a metric reader calls: the slowest
chip's seconds per iteration under `scope`, or None where the run has no
device trace or no op carries the scope (a program without the scopes).
"""
from __future__ import annotations

import bisect
import functools
import pathlib
import re

from bench import harness, tracereduce

SCOPES = ("exchange.encode", "exchange.gather", "exchange.decode")
_SCOPE = re.compile(r"(?:^|/)(exchange\.(?:encode|gather|decode))(?=/|$)")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")
WINDOW = "bench.window"
HLO_PROTO = "Hlo Proto"


def scope_of(op_name: str) -> str | None:
    """The innermost exchange scope in an `op_name` name stack, or None."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


# ---- the profiler's HLO protos (protobuf wire format, the fields needed) ----

def _fields(buf: bytes):
    """(field number, value) of one protobuf message: ints for varints,
    bytes for length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, value


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _first(buf: bytes, num: int, default=b""):
    return next((v for n, v in _fields(buf) if n == num), default)


def _module_scopes(hlo_proto: bytes) -> tuple[str, dict[str, str]]:
    """HloProto -> (module name, {instruction: scope}) over every
    computation (HloProto.hlo_module 1; HloModuleProto name 1,
    computations 3; HloComputationProto instructions 2;
    HloInstructionProto name 1, metadata 7; OpMetadata op_name 2)."""
    module = _first(hlo_proto, 1)
    scopes = {}
    for num, comp in _fields(module):
        if num != 3:
            continue
        for n, ins in _fields(comp):
            if n != 2:
                continue
            name, op_name = "", ""
            for f, v in _fields(ins):
                if f == 1:
                    name = v.decode()
                elif f == 7:
                    op_name = _first(v, 2).decode()
            scope = scope_of(op_name)
            if scope:
                scopes[name] = scope
    return _first(module, 1).decode(), scopes


def hlo_scopes(xspace: bytes) -> dict[str, dict[str, str]]:
    """{module: {instruction: scope}} from a serialized XSpace's metadata
    plane, keyed by the module event's name ("jit_per_server(<id>)") and,
    where only one proto has it, by the bare module name (XSpace.planes 1;
    XPlane name 2, event_metadata 4, stat_metadata 5; map entries key 1,
    value 2; X*Metadata id 1, name 2, stats 5; XStat metadata_id 1,
    bytes_value 6)."""
    out, bare = {}, {}
    for num, plane in _fields(xspace):
        if num != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        stat_names = {}
        for n, entry in _fields(plane):
            if n == 5:
                md = dict(_fields(_first(entry, 2)))
                stat_names[md.get(1, 0)] = md.get(2, b"").decode()
        for n, entry in _fields(plane):
            if n != 4:
                continue
            md = _first(entry, 2)
            for f, stat in _fields(md):
                st = dict(_fields(stat)) if f == 5 else {}
                if stat_names.get(st.get(1)) == HLO_PROTO and 6 in st:
                    module, scopes = _module_scopes(st[6])
                    out[_first(md, 2).decode()] = scopes
                    bare.setdefault(module, []).append(scopes)
    for module, found in bare.items():
        if len(found) == 1:
            out.setdefault(module, found[0])
    return out


# ---- ops to scopes, and seconds per scope ----------------------------------------

def assign(device: dict, scopes_by_module: dict) -> list[list]:
    """``[[scope, start_ns, end_ns], ...]`` for a chip's ops whose
    instruction, in the module event enclosing the op, has a scope."""
    modules = sorted(device["modules"], key=lambda e: e[1])
    starts = [m[1] for m in modules]
    out = []
    for name, a, b in device["ops"]:
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i < 0 or mid > modules[i][2]:
            continue
        mod = modules[i][0]
        table = (scopes_by_module.get(mod)
                 or scopes_by_module.get(mod.split("(")[0], {}))
        m = _INSTRUCTION.match(name)
        scope = table.get(m.group(1)) if m else None
        if scope:
            out.append([scope, a, b])
    return out


def extract(path) -> dict:
    """Scoped TPU op events and the window of one trace file."""
    events = tracereduce.extract(path, {WINDOW})
    by_module = hlo_scopes(pathlib.Path(path).read_bytes())
    window = next((e[1:] for e in events["host"] if e[0] == WINDOW), None)
    return {"devices": {k: assign(v, by_module)
                        for k, v in events["devices"].items()},
            "window": window}


def seconds(events: dict) -> list[dict]:
    """Per chip, the op seconds under each scope inside the window (the
    window's clip is `tracereduce`'s, as the device metrics use)."""
    if not events["window"]:
        return []
    t0, t1 = events["window"]
    chips = []
    for cid in sorted(events["devices"], key=int):
        per = dict.fromkeys(SCOPES, 0.0)
        for scope, a, b in tracereduce._clip(events["devices"][cid], t0, t1):
            per[scope] += (b - a) / 1e9
        chips.append(per)
    return chips


@functools.lru_cache(maxsize=1)
def _traced_run(path: str, mtime_ns: int) -> list[dict]:
    return seconds(extract(path))


def device_seconds(rec: dict, scope: str) -> float | None:
    """Slowest chip's device seconds per iteration under `scope`."""
    if not rec["trace"]:
        return None
    path = tracereduce.find_xplane(harness.OUT / "trace")
    per_chip = [ch[scope] for ch in _traced_run(str(path),
                                                path.stat().st_mtime_ns)]
    return max(per_chip) / rec["iters"] if any(per_chip) else None
