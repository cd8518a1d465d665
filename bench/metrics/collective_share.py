"""collective_share: device time in collective operations over the traced
window, per chip, as a percentage.  On each device plane, the union of the
``XLA Ops`` intervals of all-reduce, all-gather, reduce-scatter,
collective-permute and all-to-all operations, averaged over the planes that
ran an operation.  None where no collective ran (a cell on one chip).

An operation is a collective by its instruction name
(``all-gather-start.3``), by the opcode in its HLO instruction (the event's
``long_name`` stat, ``devtrace.Event.info``), by the fused computation it
calls (``calls=%all-reduce-scatter.1``: the TPU compiler fuses an
all-reduce's reduce-scatter half), or by its ``hlo_category``.  An
asynchronous collective runs from its ``-start`` to its ``-done``: the two
are paired in order on the plane, per kind, and the whole span counts.

Also prints to stderr the same device time split by kind, and by the
program (``XLA Modules`` event, e.g. the resident server's decode chunk
``_decode_chunk_impl`` or its admission ``_admit_impl``) it ran in."""
import bisect
import re
import sys

from bench import devtrace

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
         "all-to-all")
_ANY = "|".join(KINDS)
_NAME = re.compile(r"(%s)(-start|-done)?(\.\d+)*$" % _ANY)
_OPCODE = re.compile(r"\s(%s)(-start|-done)?\(" % _ANY)
_CALLS = re.compile(r"calls=%%?(all-reduce-scatter|%s)(-start|-done)?\b"
                    % _ANY)
_CATEGORY = re.compile(r"(?:^|\s)(%s)(?:\s|$)" % _ANY)


def kind_of(name: str, info: str = ""):
    """(kind, ``""``/``-start``/``-done``) of the collective an ``XLA Ops``
    event ran, or None: from its instruction name
    (``%all-gather-start.3 = ...`` or ``all-gather-start.3``), else from
    ``info``, the HLO instruction and category the trace gives for it."""
    m = _NAME.match(name.split(" = ")[0].strip().lstrip("%"))
    if m is None:
        text = f"{name} {info}"
        body = text.split(" = ", 1)[1] if " = " in text else text
        m = _OPCODE.search(" " + body) or _CALLS.search(body) \
            or _CATEGORY.search(info)
    if m is None:
        return None
    kind = "all-reduce" if m.group(1) == "all-reduce-scatter" else m.group(1)
    return kind, (m.group(2) or "") if m.re.groups >= 2 else ""


def intervals(events):
    """[(start_ns, end_ns, kind, first event)] of one plane's collective
    events in time order: a ``-start`` joined to the next ``-done`` of its
    kind."""
    out, open_ = [], {}
    for e, (kind, half) in sorted(events, key=lambda ek: ek[0].start_ns):
        if half == "-start":
            open_.setdefault(kind, []).append(e)
        elif half == "-done" and open_.get(kind):
            s = open_[kind].pop(0)
            out.append((s.start_ns, e.end_ns, kind, s))
        else:
            out.append((e.start_ns, e.end_ns, kind, e))
    out += [(s.start_ns, s.end_ns, k, s) for k, ss in open_.items()
            for s in ss]
    return sorted(out, key=lambda iv: iv[0])


def _program(mods, starts, t):
    """The program whose module event (sorted ``mods``, their ``starts``)
    covers time ``t`` on this plane."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < mods[i][1]:
        return devtrace.program_of(mods[i][2])
    return "no program"


def read(w):
    if not w.trace:
        return None
    planes = devtrace.busy_by_plane(w.trace)
    coll = {}
    for e in w.trace:
        if e.plane in planes and e.line == devtrace.OPS_LINE:
            k = kind_of(e.name, e.info)
            if k is not None:
                coll.setdefault(e.plane, []).append((e, k))
    if not coll:
        return None
    total = 0
    by_kind, by_program = {}, {}
    for p in planes:
        ivs = intervals(coll.get(p, []))
        total += sum(b - a for a, b in devtrace.union(
            (a, b) for a, b, _, _ in ivs))
        mods = sorted((e.start_ns, e.end_ns, e.name) for e in w.trace
                      if e.plane == p and e.line == devtrace.MODULES_LINE)
        starts = [m[0] for m in mods]
        for a, b, k, _ in ivs:
            by_kind[k] = by_kind.get(k, 0) + b - a
            prog = _program(mods, starts, a)
            by_program[prog] = by_program.get(prog, 0) + b - a
    n = len(planes)

    def fmt(by):
        return ", ".join(f"{k} {v / n / 1e9:.9f}"
                         for k, v in sorted(by.items(), key=lambda kv: -kv[1]))
    print(f"[bench] collective_share: collective device s per chip by kind: "
          f"{fmt(by_kind)}; by program: {fmt(by_program)}; over "
          f"{w.trace_window_s:.6f} s traced, {n} chips", file=sys.stderr)
    return 100.0 * total / n / 1e9 / w.trace_window_s
