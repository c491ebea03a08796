"""Profiles for the tests: built from a table of events, or a CPU run's
trace recast as one chip's."""

from __future__ import annotations


def xspace(planes: dict[str, dict[str, list[tuple[str, int, int]]]]):
    """A profile from {plane: {line: [(event, start ns, end ns)]}}."""
    from jax.profiler import ProfileData

    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names = sorted({ev for evs in lines.values() for ev, _, _ in evs})
        meta = {n: i for i, n in enumerate(names, 1)}
        body = [f'id: {pid} name: "{plane}"']
        for lid, (line, evs) in enumerate(lines.items(), 1):
            ev = " ".join(
                f"events {{ metadata_id: {meta[n]} offset_ps: {a * 1000} "
                f"duration_ps: {(b - a) * 1000} }}" for n, a, b in evs)
            body.append(f'lines {{ id: {lid} name: "{line}" '
                        f"timestamp_ns: 0 {ev} }}")
        body += [f'event_metadata {{ key: {i} value {{ id: {i} '
                 f'name: "{n}" }} }}' for n, i in meta.items()]
        out.append("planes { " + " ".join(body) + " }")
    return ProfileData.from_text_proto("\n".join(out))


def host_as_device(path: str):
    """The CPU backend's trace at ``path`` with its XLA ops (host events
    that carry an ``hlo_module`` stat) moved onto a ``/device:TPU:0``
    plane, so that the reduction's one path can run off the chip."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, ops, mods = {}, [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = []
            for ev in line.events:
                span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                module = dict(ev.stats).get("hlo_module")
                if module is None:
                    evs.append((ev.name, *span))
                    continue
                ops.append((ev.name, *span))
                name = str(module)
                mods.append((name if name.startswith("jit_")
                             else f"jit_{name}", *span))
            host[f"{line.name}#{len(host)}"] = evs
    t0 = min(s for evs in [*host.values(), ops] for _, s, _ in evs)
    shift = lambda evs: [(n, int(s - t0), int(e - t0)) for n, s, e in evs]
    return xspace({
        "/host:CPU": {k: shift(v) for k, v in host.items() if v},
        "/device:TPU:0": {"XLA Ops": shift(ops),
                          "XLA Modules": shift(mods)}})
