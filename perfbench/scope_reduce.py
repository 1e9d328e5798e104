"""From a profiler trace to the `jax.named_scope` each device operation ran
under: the map behind the `decode_*_share_pct` metrics.

On the chip an operation's event carries no scope: its name is the HLO
instruction's text (`%fusion.364 = bf16[32,18944]{...} fusion(...)`), its
stats are times. The scope is in the program: the trace keeps every
program that ran, as a serialized HloProto, in the event metadata of the
plane `/host:metadata`, and there each instruction has
`metadata={op_name="jit(...)/while/body/.../ffn/dot_general"}`. So:

  1. walk the .xplane.pb's protobuf wire format (no generated classes are
     installed here) down to those HloProto blobs, by program name;
  2. let jaxlib parse a blob and print the module as text;
  3. per instruction, take the scope from its op_name: the LAST component
     that is one of the scopes asked for (`attn/attn.kernel/...` is attn).
     A fusion the compiler left without metadata takes the scope most of
     the instructions it calls, transitively, have.

    python3 perfbench/scope_reduce.py <trace.xplane.pb> <ops.json> <scopes,comma,separated>

`ops.json`: {program name: {short op name: [seconds, count]}}, the trace
child's `ops_in_modules_s` (operations inside whole runs of a program inside
the traced window; `trace_reduce.short_name` keeps the instruction's name
first). Prints {program: {"total_s": s, "by_scope_s": {scope: s},
"unscoped_s": s, "unmapped_s": s, "top": [...]}}. Runs in a child with
JAX_PLATFORMS=cpu (jaxlib's parser is the only thing it needs of jax).
"""

from __future__ import annotations

import json
import re
import sys
from collections import Counter


def _varint(b: bytes, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if not c & 0x80:
            return r, i


def fields(b: bytes):
    """(field number, wire type, value) of one protobuf message: varints as
    int, length-delimited as bytes, fixed as bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, w = key >> 3, key & 7
        if w == 0:
            v, i = _varint(b, i)
        elif w == 2:
            ln, i = _varint(b, i)
            v = b[i : i + ln]
            i += ln
        elif w in (1, 5):
            ln = 8 if w == 1 else 4
            v = b[i : i + ln]
            i += ln
        else:
            raise ValueError(f"wire type {w} at {i}")
        yield f, w, v


def hlo_protos(xspace: bytes, plane: str = "/host:metadata") -> dict[str, bytes]:
    """program name -> serialized xla.HloProto. XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4 (map: value = 2);
    XEventMetadata.name = 2, .stats = 5; XStat.bytes_value = 6."""
    out: dict[str, bytes] = {}
    for f, w, v in fields(xspace):
        if f != 1 or w != 2:
            continue
        msg = list(fields(v))
        if not any(f2 == 2 and v2 == plane.encode() for f2, _, v2 in msg):
            continue
        for f2, w2, entry in msg:
            if f2 != 4 or w2 != 2:
                continue
            for f3, w3, meta in fields(entry):
                if f3 != 2 or w3 != 2:
                    continue
                name, blob = None, None
                for f4, w4, v4 in fields(meta):
                    if f4 == 2 and w4 == 2:
                        name = v4.decode(errors="replace")
                    elif f4 == 5 and w4 == 2:
                        for f5, w5, v5 in fields(v4):
                            if f5 == 6 and w5 == 2:
                                blob = v5
                if name and blob:
                    out[name] = blob
    return out


def hlo_text(hlo_proto: bytes) -> str:
    """The module of an xla.HloProto (field 1: hlo_module) as HLO text."""
    from jax._src.lib import xla_client

    module = next(v for f, w, v in fields(hlo_proto) if f == 1 and w == 2)
    return xla_client._xla.HloModule.from_serialized_hlo_module_proto(module).to_string()


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLEE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")


def scope_of(op_name: str, scopes: tuple[str, ...]) -> str | None:
    for part in reversed(op_name.split("/")):
        for scope in scopes:
            if part == scope or part.startswith(scope + "."):  # attn.kernel files under attn
                return scope
    return None


def instruction_scopes(text: str, scopes: tuple[str, ...]) -> dict[str, str | None]:
    """instruction name -> scope (None: it has an op_name under none of
    *scopes*). Instructions that neither carry nor call metadata are left out."""
    own: dict[str, str] = {}  # instruction -> op_name
    callees: dict[str, list[str]] = {}
    members: dict[str, list[str]] = {}  # computation -> its instructions
    current = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = m.group(1)
            members[current] = []
            continue
        m = _INSTRUCTION.match(line)
        if not m or current is None:
            continue
        name = m.group(1)
        members[current].append(name)
        op = _OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
        called = _CALLEE.findall(line)
        if called:
            callees[name] = called

    def votes(name: str, seen: set[str]) -> Counter:
        c: Counter = Counter()
        if name in own:
            c[scope_of(own[name], scopes)] += 1
        for comp in callees.get(name, ()):
            if comp in seen:
                continue
            seen.add(comp)
            for inner in members.get(comp, ()):
                c.update(votes(inner, seen))
        return c

    out: dict[str, str | None] = {}
    for name in {n for ns in members.values() for n in ns}:
        if name in own:
            out[name] = scope_of(own[name], scopes)
        elif name in callees:
            c = votes(name, set())
            if c:
                named = Counter({k: v for k, v in c.items() if k is not None})
                out[name] = named.most_common(1)[0][0] if named else None
    return out


def reduce_program(ops: dict, scopes_of: dict[str, str | None], scopes: tuple[str, ...]) -> dict:
    by = {s: 0.0 for s in scopes}
    total = unscoped = unmapped = 0.0
    rows = []
    for short, (sec, _count) in ops.items():
        name = short.split(" ")[0].lstrip("%")
        total += sec
        if name not in scopes_of:
            unmapped += sec
            scope = "?"
        elif scopes_of[name] is None:
            unscoped += sec
            scope = "-"
        else:
            scope = scopes_of[name]
            by[scope] += sec
        rows.append((sec, short, scope))
    rows.sort(key=lambda r: -r[0])
    return {
        "total_s": total, "by_scope_s": by, "unscoped_s": unscoped, "unmapped_s": unmapped,
        "top": [[short[:80], scope, sec] for sec, short, scope in rows[:12]],
    }


def main(argv) -> int:
    with open(argv[1], "rb") as f:
        protos = hlo_protos(f.read())
    with open(argv[2]) as f:
        ops_in_modules = json.load(f)
    scopes = tuple(argv[3].split(","))
    # The chip names a program `jit_f(<id>)` in both places; the CPU
    # backend of a rehearsal leaves the id out of an operation's own stat:
    # there a program matches by its bare name, if one program has it.
    bare: dict[str, list[str]] = {}
    for name in protos:
        bare.setdefault(name.split("(")[0], []).append(name)
    out = {}
    for program, ops in ops_in_modules.items():
        named = [program] if program in protos else bare.get(program, [])
        if len(named) != 1:
            continue
        out[program] = reduce_program(ops, instruction_scopes(hlo_text(protos[named[0]]), scopes), scopes)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
