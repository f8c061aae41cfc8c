"""Checkpoint / resume via orbax.

The reference has NO checkpointing (explicitly disabled,
``lightning_learner.py:188``); SURVEY §5 marks this as the idiomatic
addition. Covers both run modes:

- :func:`save_learner` / :func:`restore_learner` — one node's params,
  optimizer state and round counter;
- :meth:`SpmdFederation.save` / ``.restore`` (wired here) — the whole
  node-stacked federation state, sharding-aware (orbax restores straight
  into the mesh layout).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import orbax.checkpoint as ocp

from p2pfl_tpu.settings import Settings

Pytree = Any


def _path(directory: str) -> str:
    return os.path.abspath(os.path.expanduser(directory))


def _manager(directory: str, keep_n: Optional[int]) -> ocp.CheckpointManager:
    """A CheckpointManager with retention wired: ``keep_n`` newest steps
    are kept (``CheckpointManagerOptions.max_to_keep``), older ones GC'd
    on save. None reads ``Settings.CHECKPOINT_KEEP_N``; 0 = unbounded —
    the pre-retention behavior, still the standalone default, but a
    long-lived fleet member saving every update MUST bound this (the
    node journal passes its own ``JOURNAL_KEEP_N``)."""
    if keep_n is None:
        keep_n = int(Settings.CHECKPOINT_KEEP_N)
    options = ocp.CheckpointManagerOptions(max_to_keep=keep_n) if keep_n > 0 else None
    return ocp.CheckpointManager(_path(directory), options=options)


def save_state(
    directory: str, state: dict, step: int = 0, keep_n: Optional[int] = None
) -> None:
    """Save an arbitrary pytree-of-arrays state dict."""
    with _manager(directory, keep_n) as mgr:
        mgr.save(step, args=ocp.args.StandardSave(state), force=True)
        mgr.wait_until_finished()


def restore_state(directory: str, template: dict, step: Optional[int] = None) -> dict:
    """Restore into the structure/shardings of ``template``."""
    with ocp.CheckpointManager(_path(directory)) as mgr:
        step = mgr.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {directory}")
        return mgr.restore(step, args=ocp.args.StandardRestore(template))


def save_learner(
    directory: str,
    learner,
    round: Optional[int] = None,  # noqa: A002
    keep_n: Optional[int] = None,
) -> None:
    save_state(
        directory,
        {"params": learner.params, "opt_state": learner.opt_state},
        step=round or 0,
        keep_n=keep_n,
    )


def restore_learner(directory: str, learner, step: Optional[int] = None) -> None:
    state = restore_state(
        directory, {"params": learner.params, "opt_state": learner.opt_state}, step
    )
    learner.params = state["params"]
    learner.opt_state = state["opt_state"]


def _federation_state(fed) -> dict:
    """Everything a resumed federation needs: params + opt state + any
    algorithm state (SCAFFOLD control variates, FedOpt server moments) —
    dropping those on resume would silently degrade the algorithm. A
    federation that keeps no optimizer state between rounds
    (``SpmdLoraFederation(keep_opt_state=False)``: ``opt_state`` is ``None``)
    has none to save."""
    state = {"params": fed.params}
    if fed.opt_state is not None:
        state["opt_state"] = fed.opt_state
    if getattr(fed, "scaffold", False):
        state["c_global"] = fed.c_global
        state["c_local"] = fed.c_local
    if getattr(fed, "server_opt", ""):
        state["opt_m"] = fed.opt_m
        state["opt_v"] = fed.opt_v
        state["server_t"] = fed._server_t
    return state


def save_federation(directory: str, fed) -> None:
    save_state(directory, _federation_state(fed), step=fed.round)


def restore_federation(directory: str, fed, step: Optional[int] = None) -> None:
    with ocp.CheckpointManager(_path(directory)) as mgr:
        use = mgr.latest_step() if step is None else step
        if use is None:
            raise FileNotFoundError(f"no checkpoint under {directory}")
        state = mgr.restore(use, args=ocp.args.StandardRestore(_federation_state(fed)))
    fed.params = state["params"]
    fed.opt_state = state.get("opt_state")
    if getattr(fed, "scaffold", False):
        fed.c_global = state["c_global"]
        fed.c_local = state["c_local"]
    if getattr(fed, "server_opt", ""):
        fed.opt_m = state["opt_m"]
        fed.opt_v = state["opt_v"]
        fed._server_t = int(state["server_t"])
    fed.round = use
