"""Collective-consistency pass over captured ``shard_map`` programs.

Three rules, all aimed at the SPMD failure mode that matters at scale
(a deadlock every PE sits in silently):

* ``SPMD001`` — a collective (``psum``/``all_gather``/``all_to_all``/
  ``ppermute``/...) names an axis the enclosing ``shard_map`` mesh
  does not declare.
* ``SPMD002`` — the branches of a ``lax.cond``/``switch`` inside a
  ``shard_map`` body issue different collective sequences: whichever
  branch a PE takes, its peers must issue the *same* collectives in
  the same order or the program deadlocks.
* ``SPMD003`` — a ``shard_map`` site staged with ``check=False``
  (jax's own replication checker disabled) that is not recorded in the
  reviewed ``analysis/allowlist.toml`` with a reason.
"""

from __future__ import annotations

from typing import Any, Iterator, List, Tuple

from ..dist.compat import CHECK_KW
from .findings import Finding, Report, rel_to_repo

# primitives that communicate across a named mesh axis
COLLECTIVE_PRIMS = {
    "psum",
    "psum2",
    "pbroadcast",
    "pmax",
    "pmin",
    "all_gather",
    "all_to_all",
    "ppermute",
    "pshuffle",
    "reduce_scatter",
    "axis_index",
}
# collectives whose sequence must agree across PEs for progress (the
# replication bookkeeping prims psum2 emits alongside are excluded)
BLOCKING_PRIMS = COLLECTIVE_PRIMS - {"axis_index", "pbroadcast"}


def _as_closed(jaxpr: Any) -> Any:
    return jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr


def _sub_jaxprs(eqn: Any) -> Iterator[Tuple[str, Any]]:
    """Yield ``(param_name, jaxpr)`` for every subjaxpr of ``eqn``."""
    for key, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for item in vals:
            if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                yield key, _as_closed(item)


def _source_site(eqn: Any) -> Tuple[str, int, str]:
    """(repo-relative file, line, function) of an eqn's user frame."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
    except Exception:
        frame = None
    if frame is None:
        return "", 0, ""
    return (
        rel_to_repo(frame.file_name),
        int(frame.start_line),
        # frames carry the qualified name (``outer.<locals>.inner``);
        # allowlist entries name the innermost function
        frame.function_name.rsplit(".", 1)[-1],
    )


def _axis_names(eqn: Any) -> List[str]:
    """Named mesh axes a collective eqn communicates over."""
    params = eqn.params
    raw: Any = ()
    for key in ("axes", "axis_name", "axis_index_groups_axis"):
        if key in params and params[key] is not None:
            raw = params[key]
            break
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return [a for a in raw if isinstance(a, str)]


def _mesh_axes(shard_map_eqn: Any) -> Tuple[str, ...]:
    mesh = shard_map_eqn.params.get("mesh")
    names = getattr(mesh, "axis_names", None)
    if names is None:
        return ()
    return tuple(str(a) for a in names)


def iter_shard_maps(jaxpr: Any) -> Iterator[Any]:
    """Yield every ``shard_map`` eqn reachable from ``jaxpr``."""
    for eqn in _as_closed(jaxpr).eqns:
        if eqn.primitive.name == "shard_map":
            yield eqn
        for _, sub in _sub_jaxprs(eqn):
            yield from iter_shard_maps(sub)


def collective_signature(jaxpr: Any) -> Tuple:
    """Ordered tuple of blocking collectives issued by ``jaxpr``.

    Branch-divergence inside is folded in recursively: a nested cond
    contributes its (already checked) first-branch signature.
    """
    sig: List = []
    for eqn in _as_closed(jaxpr).eqns:
        name = eqn.primitive.name
        if name in BLOCKING_PRIMS:
            sig.append((name, tuple(_axis_names(eqn))))
            continue
        for _, sub in _sub_jaxprs(eqn):
            sig.extend(collective_signature(sub))
            if name == "cond":
                break  # branches checked separately; count one
    return tuple(sig)


def _check_body(
    body: Any,
    mesh_axes: Tuple[str, ...],
    entry: str,
    report: Report,
) -> None:
    for eqn in _as_closed(body).eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            for axis in _axis_names(eqn):
                if axis not in mesh_axes:
                    file, line, func = _source_site(eqn)
                    report.add(
                        Finding(
                            rule="SPMD001",
                            pass_name="collectives",
                            message=(
                                f"{name} over undeclared axis "
                                f"{axis!r} (mesh axes: {mesh_axes})"
                            ),
                            file=file,
                            line=line,
                            function=func,
                            entry=entry,
                        )
                    )
        if name == "cond":
            branches = eqn.params.get("branches", ())
            sigs = [collective_signature(b) for b in branches]
            if len(set(sigs)) > 1:
                file, line, func = _source_site(eqn)
                report.add(
                    Finding(
                        rule="SPMD002",
                        pass_name="collectives",
                        message=(
                            "cond branches issue different collective "
                            f"sequences {sigs} — SPMD deadlock if PEs "
                            "diverge"
                        ),
                        file=file,
                        line=line,
                        function=func,
                        entry=entry,
                    )
                )
        for _, sub in _sub_jaxprs(eqn):
            _check_body(sub, mesh_axes, entry, report)


def run(
    jaxprs: List[Tuple[str, Any]],
    report: Report,
    expect_shard_maps: bool = False,
) -> int:
    """Check every captured program; returns shard_map sites seen."""
    sites = 0
    for item in jaxprs:
        entry, jaxpr = item[0], item[1]
        hint = item[2] if len(item) > 2 else None
        found = False
        for sm in iter_shard_maps(jaxpr):
            found = True
            sites += 1
            mesh_axes = _mesh_axes(sm)
            file, line, func = _source_site(sm)
            if hint is not None and (
                not file or file.startswith("src/repro/analysis/")
            ):
                # the shard_map eqn was bound under the tracing proxy;
                # anchor it on the patched builder the entry came from
                file, line, func = hint[0], 0, hint[1]
            if sm.params.get(CHECK_KW, True) is False:
                report.add(
                    Finding(
                        rule="SPMD003",
                        pass_name="collectives",
                        message=(
                            "shard_map staged with check=False "
                            "(replication checking disabled) — must "
                            "be allowlisted with a reason"
                        ),
                        file=file,
                        line=line,
                        function=func,
                        entry=entry,
                    )
                )
            _check_body(sm.params["jaxpr"], mesh_axes, entry, report)
        if expect_shard_maps and not found and entry.startswith("dist_"):
            report.note(
                f"{entry}: no shard_map equation captured — tracing "
                "registry may be stale"
            )
    return sites
