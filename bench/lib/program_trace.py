"""The program's own trace, read from the profile the harness just took.

The program marks its work in two ways (README.md, "Tracing"):

* ``fed.*`` named scopes (``jax.named_scope``) reach each compiled op's
  ``op_name`` metadata.  The chip's ``XLA Ops`` events carry no such stat
  (only the op's HLO text and times), so the scope of an op is looked up
  in the optimized HLO module the profiler writes into its
  ``/host:metadata`` plane (one ``Hlo Proto`` per program), keyed by the
  program (the enclosing ``XLA Modules`` event) and the op's instruction
  name;
* ``fed.*`` host spans (``jax.profiler.TraceAnnotation``) in the session
  driver, each with the window it belongs to (``window``) and what it
  moved (``bytes``, ``rounds``), on the device trace's clock.

``load`` reduces the same ``.xplane.pb`` that ``trace.load`` read to plain
tuples; the functions below it are pure, so the CPU tests feed them
synthetic events.  A program without these marks (the parent of the
change that added them) leaves every list empty or every scope blank,
and the readers built on this return ``None``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

from bench.lib import registry
from bench.lib import trace as tr

TRACE_DIR = os.path.join(registry.BENCH, ".out", "trace")
PREFIX = "fed."
SCOPE = re.compile(r"fed\.[a-z_]+")
COMPILE = "backend_compile"      # JAX's own host events: backend_compile*
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
STAGE = ("fed.sample", "fed.h2d")
MODEL = ("fed.fakes", "fed.d_update", "fed.g_update")
STORE = ("fed.store_gather", "fed.store_scatter", "fed.window_mask")
_INSTR = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*?op_name="([^"]*)"', re.M)


@dataclasses.dataclass
class ProgramTrace:
    spans: list        # [(name, start_ns, end_ns, {arg: value})] fed.* spans
    compiles: list     # [(name, start_ns, end_ns)] backend_compile* events
    devices: dict      # chip plane -> [(scope or "", start_ns, end_ns)]


# -- the HLO modules in the profile ---------------------------------------

def _varint(b, i: int) -> tuple:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, value) of each field of a serialized protobuf
    message: an int for a varint or fixed field, a memoryview for a
    length-delimited one."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = int.from_bytes(b[i:i + size], "little"), i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _hlo_modules(xspace) -> dict:
    """{program event name (``jit_chunk(12)``): serialized HloModuleProto}
    from the metadata plane of a serialized ``XSpace``: XSpace.planes (1);
    XPlane.name (2), .event_metadata (4) and .stat_metadata (5), both maps
    (key 1, value 2); XEventMetadata.name (2) and .stats (5); XStat
    .metadata_id (1) and .bytes_value (6); HloProto.hlo_module (1)."""
    for _, plane in (f for f in _fields(xspace) if f[0] == 1):
        fields = list(_fields(plane))
        names = [bytes(v).decode() for k, v in fields if k == 2]
        if names != [METADATA_PLANE]:
            continue
        stat_ids = set()
        for _, entry in (f for f in fields if f[0] == 5):
            meta = dict(_fields(dict(_fields(entry))[2]))
            if bytes(meta.get(2, b"")).decode() == HLO_PROTO_STAT:
                stat_ids.add(meta.get(1))
        out = {}
        for _, entry in (f for f in fields if f[0] == 4):
            meta = list(_fields(dict(_fields(entry))[2]))
            name = "".join(bytes(v).decode() for k, v in meta if k == 2)
            for _, stat in (f for f in meta if f[0] == 5):
                st = dict(_fields(stat))
                if st.get(1) in stat_ids and 6 in st:
                    out[name] = bytes(dict(_fields(st[6]))[1])
        return out
    return {}


def scopes_of_module(hlo_text: str) -> dict:
    """{instruction name: innermost ``fed.*`` scope of its op_name} of one
    HLO module's text; instructions with no scope are left out."""
    out = {}
    for name, op_name in _INSTR.findall(hlo_text):
        found = SCOPE.findall(op_name)
        if found:
            out[name] = found[-1]
    return out


def hlo_scopes(xspace) -> dict:
    """{program event name: {instruction name: scope}} of every HLO
    module in a serialized ``XSpace``."""
    from jax._src.lib import xla_client
    parse = xla_client._xla.HloModule.from_serialized_hlo_module_proto
    return {name: scopes_of_module(parse(proto).to_string())
            for name, proto in _hlo_modules(xspace).items()}


def scoped_ops(ops, modules, scopes) -> list:
    """``(scope, start, end)`` of each op event ``(HLO text, start, end)``:
    the scope its instruction carries in the program whose ``XLA Modules``
    event ``(name, start, end)`` encloses it.  A program event's name is
    matched whole, else by the program name before its ``(id)`` where one
    module alone has that name."""
    by_base: dict = {}
    for name in scopes:
        by_base.setdefault(name.split("(")[0], []).append(name)

    def table(module: str) -> dict:
        if module in scopes:
            return scopes[module]
        same = by_base.get(module.split("(")[0], [])
        return scopes[same[0]] if len(same) == 1 else {}

    mods = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for text, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(mods) and mods[j][2] <= s:
            j += 1
        inside = j < len(mods) and mods[j][1] <= s
        instr = text.split(" = ", 1)[0].lstrip("%")
        out.append((table(mods[j][0]).get(instr, "") if inside else "",
                    s, e))
    return out


def load(log_dir: str = TRACE_DIR) -> ProgramTrace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        return ProgramTrace([], [], {})
    with open(paths[-1], "rb") as f:
        xspace = f.read()
    scopes = hlo_scopes(memoryview(xspace))
    spans, compiles, devices = [], [], {}
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        if tr.CHIP_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name in (tr.OPS_LINE, MODULES_LINE):
                    (ops if line.name == tr.OPS_LINE else modules).extend(
                        (e.name, float(e.start_ns), float(e.end_ns))
                        for e in line.events)
            devices[plane.name] = scoped_ops(ops, modules, scopes)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.end_ns), dict(e.stats)))
                    elif e.name.startswith(COMPILE):
                        compiles.append((e.name, float(e.start_ns),
                                         float(e.end_ns)))
    return ProgramTrace(sorted(spans, key=lambda s: s[1]), compiles, devices)


def of(r) -> ProgramTrace:
    """The program trace of readings ``r``, loaded once per run."""
    pt = getattr(r, "program_trace", None)
    if pt is None:
        pt = r.program_trace = load()
    return pt


# ---------------------------------------------------------------------------
# reduction (pure functions of event tuples)
# ---------------------------------------------------------------------------

def inherit(ops) -> list:
    """``(scope, start, end)`` with each op that carries no scope given
    that of the innermost op enclosing it on the same line: the body of a
    loop the compiler built for a scoped op (a scatter's row loop, the
    top-k bisection) belongs to that op's scope."""
    out, stack = [], []              # stack: [(scope, end)] enclosing ops
    for scope, s, e in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        # an op that ended, or that this one only partly overlaps, is done
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            stack.pop()
        if not scope and stack:
            scope = stack[-1][0]
        out.append((scope, s, e))
        stack.append((scope, e))
    return out


def scope_ns(pt: ProgramTrace, lo: float, hi: float, scopes) -> float:
    """Device self time of the ops in ``scopes`` inside [lo, hi], summed
    over chips (a loop op's self time leaves out the ops of its body)."""
    total = 0.0
    for ops in pt.devices.values():
        own = tr.self_times(inherit(ops), lo, hi)
        total += sum(ns for name, ns in own.items() if name in scopes)
    return total


def innermost(spans, lo: float, hi: float) -> list:
    """[(name, start, end)]: [lo, hi] cut where the innermost open span
    changes, each piece named by that span (``""`` where none is open)."""
    cuts = sorted({lo, hi} | {t for _, s, e, *_ in spans for t in (s, e)
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        open_ = [(e - s, n) for n, s, e, *_ in spans if s <= mid <= e]
        out.append((min(open_)[1] if open_ else "", a, b))
    return out


def idle_ns_by_span(pt: ProgramTrace, lo: float, hi: float) -> dict:
    """Per innermost ``fed.*`` span, the device-idle time inside [lo, hi]
    while it was open, summed over chips."""
    pieces = innermost(pt.spans, lo, hi)
    tot: dict = {}
    for ops in pt.devices.values():
        j = 0
        for g0, g1 in tr.idle_gaps(ops, lo, hi):
            while pieces[j][2] <= g0:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][1] < g1:
                name, a, b = pieces[k]
                if name:
                    tot[name] = tot.get(name, 0.0) + min(b, g1) - max(a, g0)
                k += 1
    return tot


def spans_in(pt: ProgramTrace, lo: float, hi: float, names=None) -> list:
    """The ``fed.*`` spans that overlap [lo, hi], of ``names`` if given."""
    return [sp for sp in pt.spans if sp[2] > lo and sp[1] < hi
            and (names is None or sp[0] in names)]


def window_share(ns: float, r) -> float:
    """``ns``, summed over chips, as a share of the traced window per
    chip, in %."""
    return 100.0 * ns / max(r.chips, 1) / (r.window_s * 1e9)
