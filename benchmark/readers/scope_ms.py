"""Milliseconds of device self time per unit, of the operations the program
itself names: ``opendiloco_tpu.obs.programs.tables()`` gives each compiled
program's instructions by scope, pass and opcode, a trace's event is joined to
them by result name and result shape, and the seconds of the events that
``params`` asks for, the mean over the traced devices, are divided by the
spans ``params["per"]`` names in the capture the program kept
(``obs.capture.last()``; those that started inside it).

``params``: ``program``, a program's name or, ending in ``/``, a prefix
(``prefill/``; left out: any); at most one of ``scope`` (a name or a list: an
instruction counts where one of them stands anywhere along its path of
``odtp_*`` scopes), ``pass`` (``fwd`` | ``bwd`` | ``remat``), ``opcodes``
(prefixes of opcodes) and ``unscoped`` true (under no ``odtp_*`` scope), none
of them for the whole program; ``per``, the span that counts the units
(``inner/dispatch``: a step; ``serve_prefill``: an admission). A scope's and
the unscoped operations leave the collectives out (``opcodes`` asks for those),
so that the scopes, the unscoped rest and the collectives tile a program.

A trace's event carries no program. An event whose name and shape several
instructions have is counted where all of them are what ``params`` asks for,
not counted where none is, and otherwise left out (``left_out_s`` on the
line, a device): so a reading can be short and never holds another program's
time.

Each reading prints a line ``scope_ms`` with what it summed and divided, the
devices one by one; the first of a run also prints ``scope_seconds``: the
whole join (``benchmark/SCOPES.md``). Nothing, and no exception, where the run
traced nothing, the program has no ``obs.programs`` (a parent of PR 51), no
program could be lowered, the capture holds no such span or no event is what
``params`` asks for."""

import time

from odbench import xplane

TOP = 20
_KEY = "scope_ms"  # where a run's join is kept between its readings


def _program_obs():
    try:
        from opendiloco_tpu import obs as program
    except Exception:
        return None, None
    return program, getattr(program, "programs", None)


def _span_counts(program) -> dict:
    """{span name: [how many started inside the newest capture, their attributes]}"""
    last = getattr(program.capture, "last", None)
    capture = None if last is None else last()
    counts: dict = {}
    if capture is not None:
        for s in capture.spans:
            if s["t0"] >= capture.anchor_pc:
                counts.setdefault(s["name"], []).append(s["args"])
    return counts


def _group(name: str) -> str:
    """``prefill/512`` -> ``prefill``, ``train_step#2`` -> ``train_step``"""
    return name.split("#")[0].split("/")[0]


def join(obs):
    """The run's join, made once: per traced device the self nanoseconds and
    the events of each (result name, result shape), and what each pair can be
    -> a dict, or None where there is nothing to join."""
    if _KEY in obs:
        return obs[_KEY]
    obs[_KEY] = None
    trace = obs.get("trace")
    program, programs = _program_obs()
    if not trace or not trace.get("ops") or programs is None:
        return None
    t = time.perf_counter()
    found = programs.tables()
    naming_s = time.perf_counter() - t
    by_pair = programs.index(found)
    devices = {}
    for device in sorted(trace["ops"]):
        pairs: dict = {}
        for name, self_ns, detail in xplane.self_times(trace["ops"][device]):
            held = pairs.setdefault((name.split(" ")[0], programs.result_shape(detail)), [0.0, 0])
            held[0] += self_ns
            held[1] += 1
        devices[device] = pairs
    joined = {
        "programs": programs, "tables": found, "by_pair": by_pair, "devices": devices,
        "spans": _span_counts(program), "naming_s": naming_s,
    }
    if obs.get("report") is not None:
        obs["report"].line("scope_seconds", **_whole(joined, trace))
    if found:
        obs[_KEY] = joined
    return obs[_KEY]


def _whole(joined, trace) -> dict:
    """The ``scope_seconds`` line: the first device's self seconds, every
    event under the one (program, path, pass) it can be, or ambiguous, or
    unmatched."""
    programs, by_pair = joined["programs"], joined["by_pair"]
    first = sorted(joined["devices"])[0]
    by_program: dict = {}
    ambiguous = {"seconds": 0.0, "events": 0, "pairs": 0}
    unmatched = {"seconds": 0.0, "events": 0, "pairs": 0}
    ranked = []
    for pair, (ns, events) in joined["devices"][first].items():
        held = by_pair.get(pair)
        seconds = ns / 1e9
        kinds = {(_group(p), ins.path, ins.pass_) for p, ins in held or ()}
        if len(kinds) != 1:
            into = unmatched if not held else ambiguous
            into["seconds"] += seconds
            into["events"] += events
            into["pairs"] += 1
            ranked.append((seconds, pair, "unmatched" if not held else "ambiguous", None))
            continue
        (group, path, pass_), ins = next(iter(kinds)), held[0][1]
        entry = by_program.setdefault(group, {
            "self_s": 0.0, "events": 0, "by_scope_pass": {}, "by_opcode": {},
            "unscoped_s": 0.0, "collective_s": 0.0,
        })
        entry["self_s"] += seconds
        entry["events"] += events
        collective = programs.is_collective(ins.opcode)
        if collective:
            entry["collective_s"] += seconds
        elif not path:
            entry["unscoped_s"] += seconds
        label = f"{path or '-'}|{pass_}"
        entry["by_scope_pass"][label] = entry["by_scope_pass"].get(label, 0.0) + seconds
        entry["by_opcode"][ins.opcode] = entry["by_opcode"].get(ins.opcode, 0.0) + seconds
        ranked.append((seconds, pair, group, ins))
    for entry in by_program.values():
        for key in ("by_scope_pass", "by_opcode"):
            entry[key] = dict(sorted(entry[key].items(), key=lambda kv: -kv[1])[:16])
    ranked.sort(key=lambda r: -r[0])
    top = [
        [name, shape, seconds, program, *(
            [ins.path or "-", ins.pass_, ins.opcode] if ins is not None else [])]
        for seconds, (name, shape), program, ins in ranked[:TOP]
    ]
    found = joined["tables"]
    steps = [a["step"] for a in joined["spans"].get("inner/dispatch", []) if "step" in a]
    scopes = sorted({s for ins_list in found.values() for ins in ins_list
                     for s in ins.path.split("/") if s})
    return {
        "naming_s": joined["naming_s"],
        "programs": {name: len(ins) for name, ins in found.items()},
        "missing": getattr(found, "missing", {}),
        "ambiguous_pairs_in_tables": len(programs.ambiguous(found)),
        "scopes_seen": scopes, "devices": len(joined["devices"]), "first_device": first,
        "spans": {name: len(args) for name, args in joined["spans"].items()
                  if name in ("inner/dispatch", "serve_prefill", "serve_decode")},
        "steps": [min(steps), max(steps)] if steps else None,
        "self_s": sum(ns for ns, _ in joined["devices"][first].values()) / 1e9,
        "busy_s": (trace.get("busy_s_per_device") or [trace.get("busy_s")])[0],
        "by_program": by_program, "ambiguous": ambiguous, "unmatched": unmatched,
        "top": top,
    }


def _wanted(params, is_collective):
    """-> f(program name, instruction) -> is it what ``params`` asks for"""
    name = params.get("program")
    scopes = params.get("scope")
    scopes = [scopes] if isinstance(scopes, str) else scopes
    pass_, opcodes = params.get("pass"), tuple(params.get("opcodes") or ())
    unscoped = bool(params.get("unscoped"))

    def wanted(program, ins) -> bool:
        base = program.split("#")[0]
        if name and not (base.startswith(name) if name.endswith("/") else base == name):
            return False
        if scopes:
            along = ins.path.split("/")
            return any(s in along for s in scopes) and not is_collective(ins.opcode)
        if unscoped:
            return not ins.path and not is_collective(ins.opcode)
        if pass_:
            return ins.pass_ == pass_
        if opcodes:
            return ins.opcode.startswith(opcodes)
        return True

    return wanted


def read(obs, params):
    try:
        joined = join(obs)
    except Exception as e:  # run.py calls a reader unguarded
        if obs.get("report") is not None:
            obs["report"].line("scope_ms", params=params, error=repr(e))
        return None
    if joined is None:
        return None
    wanted = _wanted(params, joined["programs"].is_collective)
    by_pair = joined["by_pair"]
    instructions = sum(
        1 for program, found in joined["tables"].items() for ins in found
        if wanted(program, ins)
    )
    per_device, events, left_out, opcodes = [], 0, 0.0, {}
    for pairs in joined["devices"].values():
        seconds = 0.0
        for pair, (ns, n) in pairs.items():
            held = by_pair.get(pair)
            if not held:
                continue
            hits = [wanted(program, ins) for program, ins in held]
            if all(hits):
                seconds += ns / 1e9
                events += n
                if params.get("opcodes"):
                    entry = opcodes.setdefault(held[0][1].opcode, [0, 0.0])
                    entry[0] += n
                    entry[1] += ns / 1e9
            elif any(hits):
                left_out += ns / 1e9
        per_device.append(seconds)
    units = len(joined["spans"].get(params["per"], []))
    value = None
    if units and events:
        value = 1e3 * sum(per_device) / len(per_device) / units
    left_out /= len(per_device)
    if obs.get("report") is not None:
        obs["report"].line(
            "scope_ms", params=params, instructions=instructions, events=events,
            self_s_per_device=per_device, left_out_s=left_out, units=units,
            ms_per_unit_per_device=[1e3 * s / units for s in per_device] if units else None,
            **({"opcodes": opcodes} if opcodes else {}), value=value,
        )
    return value
