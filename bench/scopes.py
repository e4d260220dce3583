"""The benchmark's rule from a compiled op to the part of the training step
it belongs to, read from the ``jax.named_scope``s the program puts on its
stage kernel (``op_name`` metadata of each HLO instruction):

* ``forward``: under ``bet.forward`` and not its transpose;
* ``backward``: under ``transpose(...bet.forward...)``, or remat's
  recompute of it (``rematted_computation``);
* ``optimizer``: under ``bet.optimizer`` (AdamW and the gradient norm);
* ``probe``: under ``bet.probe`` (the full-data loss each step);
* ``batch``: under ``bet.batch`` (the mini-batch gather).

The program gives only the raw op_names (``repro.obs.profile.
stage_op_names``); this file is what classes them, so a program change
cannot move the yardstick.  An instruction whose op_names fall in none of
these, or in more than one, is unscoped, and so is an instruction name that
two stage-kernel executables of the window class differently.
"""
from __future__ import annotations

SCOPES = ("forward", "backward", "optimizer", "probe", "batch")

_MAPS: dict = {}        # id of a run's reduction -> (it, its executables' maps)


def scope_of(op_name: str) -> str | None:
    parts = op_name.split("/")
    if "bet.probe" in parts:
        return "probe"
    fwd = [i for i, p in enumerate(parts) if "bet.forward" in p]
    if fwd:
        if parts[fwd[0]].startswith("transpose(") or \
                "rematted_computation" in parts[fwd[0]:]:
            return "backward"
        return "forward"
    if "bet.optimizer" in parts:
        return "optimizer"
    if "bet.batch" in parts:
        return "batch"
    return None


def instruction_scope(op_names) -> str | None:
    scopes = {scope_of(n) for n in op_names} - {None}
    return scopes.pop() if len(scopes) == 1 else None


def scope_map(maps) -> dict:
    """``{instruction: scope or None}`` over the executables' op-name maps."""
    out: dict = {}
    for m in maps:
        for inst, names in m.items():
            s = instruction_scope(names)
            out[inst] = s if out.get(inst, s) == s else None
    return out


def scope_seconds(op_seconds: dict, maps) -> dict:
    """Device self seconds of the stage kernels' instructions per scope,
    and ``unscoped`` for the rest of them; ops of other programs are left
    out."""
    scopes = scope_map(maps)
    out = dict.fromkeys(SCOPES + ("unscoped",), 0.0)
    for name, s in op_seconds.items():
        if name in scopes:
            out[scopes[name] or "unscoped"] += s
    return out


def stage_op_names(rec):
    """The program's op-name maps of the stage-kernel executables run in
    the traced window, or None where the program has no such map."""
    try:
        from repro.obs import profile
    except ImportError:
        return None
    read = getattr(profile, "stage_op_names", None)
    if read is None or not rec.spans:
        return None
    red, maps = _MAPS.get(id(rec.reduction), (None, None))
    if red is not rec.reduction:
        maps = read(since=min(t for _, t, _ in rec.spans))
        _MAPS[id(rec.reduction)] = (rec.reduction, maps)
    return maps or None


def step_ms(rec, scope: str):
    """Milliseconds of device self time in ``scope`` per optimizer step in
    the traced window."""
    steps = rec.counts.get("steps_in_window")
    if rec.reduction is None or not steps:
        return None
    maps = stage_op_names(rec)
    if maps is None:
        return None
    return 1000.0 * scope_seconds(rec.reduction.op_seconds, maps)[scope] / steps
