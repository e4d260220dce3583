"""LM workload adapters — the pjit train step behind the BatchOptimizer
protocol, the probe objective, and the host-slice reference dataset.

Moved here from launch/train.py so the session builder (api/session.py)
and the CLI both compose the LM path through one definition; the CLI is
now a thin argparse -> RunSpec translation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..data.device_window import probe_rows, rotation_rows
from ..models import transformer as T
from ..optim.api import BatchOptimizer


@dataclasses.dataclass(frozen=True)
class LMStepOptimizer(BatchOptimizer):
    """The pjit LM train step as a BatchOptimizer over token windows.

    ``data`` is the resident (n_t, seq_len+1) token window; the step gathers
    a rotating mini-batch from it on device, so whole stages scan without
    host round-trips.  ``reset_memory`` is inherited as the identity: Adam
    moments survive batch expansions (the LM objective is stochastic per
    batch anyway, so stage boundaries do not invalidate them)."""
    train_step: Callable = None
    init_opt: Callable = None
    batch_size: int = 8
    name: str = "adamw_lm"

    def init(self, params):
        return {"opt": self.init_opt(params), "t": jnp.int32(0)}

    def step(self, params, state, objective, data):
        # ``data`` is a host-path (n_t, L) slice, the plane's fixed-capacity
        # MaskedWindow (both: rotation through the valid prefix gathers
        # identical rows), or the multi-host stacked HostWindows — there each
        # host rotates through its *own* lane and the global batch is the
        # concatenation of the per-host sub-batches (dist data parallelism).
        # One lane-aware gather serves all three (data/device_window.py).
        with jax.named_scope("bet.batch"):
            rows = rotation_rows(data, self.batch_size, state["t"])
            batch = {"tokens": rows[:, :-1], "labels": rows[:, 1:]}
        params, opt, metrics = self.train_step(params, state["opt"], batch)
        return params, {"opt": opt, "t": state["t"] + 1}, {"f": metrics["loss"]}


@dataclasses.dataclass
class TokenWindows:
    """Host-slice view of a pre-permuted token corpus: nested prefix windows
    of one permutation (§3.3's data-access contract).  The reference path
    the streaming plane is held bit-exact against (``plane="host"``)."""
    tokens: Any                    # (N, seq_len+1) int32, device

    @property
    def n(self) -> int:
        return int(self.tokens.shape[0])

    def window(self, n_t: int):
        return self.tokens[:n_t]


def make_lm_objective(cfg, eval_rows: int = 64, *, impl: str = "xla"):
    """loss(params, token block) on a fixed-size probe of the block.

    The probe is always ``eval_rows`` rows rotating through the block's
    valid prefix (``% n_valid``), so host-path slices and the plane's
    fixed-capacity MaskedWindow compute the identical batch — windows
    smaller than the probe wrap instead of shrinking it, keeping the
    two-track condition (3) comparison at a constant sample size and the
    two data paths bit-exact against each other.  ``impl`` picks the layer
    implementation (``"pallas"`` routes scan/attention blocks through the
    kernels), matching the train step so the probe measures the same
    function the optimizer descends."""
    def objective(params, toks):
        # host-path slices, MaskedWindows, and multi-host stage windows all
        # probe through the one lane-aware gather (an equal per-lane share)
        probe = probe_rows(toks, eval_rows)
        batch = {"tokens": probe[:, :-1], "labels": probe[:, 1:]}
        return T.loss_fn(cfg, params, batch, impl=impl)[0]
    return objective
