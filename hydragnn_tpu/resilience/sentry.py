"""Non-finite sentry: host-side policy over the on-device step guard.

The device half lives in the jitted step
(``train/state.py:make_train_step(guard_nonfinite=True)``): a cheap
``isfinite(loss) & isfinite(global_norm(grads))`` check that SKIPS the
offending batch — parameters, optimizer state, BatchNorm statistics
and the step counter all keep their previous values — and threads a
consecutive-bad counter through as a device scalar, so the steady
state pays no extra host sync (the skip accounting materializes once
per epoch with the loss metrics, the same discipline as
``_MetricAccum``).

This class is the host half: it accumulates the per-step bad flags,
finalizes them at epoch end, and decides when skipping is no longer
enough. A run whose epoch ENDS on ``patience`` consecutive bad steps
is not going to self-heal — the sentry then rolls back to the last
good checkpoint with a reduced learning rate (``rollback`` flight
event) instead of continuing from weights that produce non-finite
grads; after ``max_rollbacks`` of those it raises
:class:`~hydragnn_tpu.resilience.preempt.NonFiniteRollbackExhausted`
(a deterministic data/model problem the restart supervisor fail-fasts
on). Isolated bad batches mid-epoch are skipped and counted
(``train.nonfinite_skipped`` in the obs registry) without rollback —
the weights were never touched by them.
"""

from __future__ import annotations

from typing import List, Optional, Tuple


class NonFiniteSentry:
    """Per-run skip accounting + rollback policy (one per training run).

    Config (``Training`` section): ``nonfinite_patience`` (consecutive
    bad steps at an epoch's tail that trigger rollback),
    ``nonfinite_max_rollbacks``, ``nonfinite_rollback_lr_factor``.
    """

    def __init__(
        self,
        patience: int = 16,
        max_rollbacks: int = 2,
        lr_factor: float = 0.5,
    ):
        import jax.numpy as jnp

        self.patience = int(patience)
        self.max_rollbacks = int(max_rollbacks)
        self.lr_factor = float(lr_factor)
        self.rollbacks = 0
        self.skipped_total = 0
        # device scalar threaded through the guarded step: number of
        # consecutive bad steps ending at the current step
        self.consec = jnp.zeros((), jnp.int32)
        self._bads: List = []
        self._finalized: Optional[Tuple[int, int]] = None

    def epoch_start(self) -> None:
        self._bads = []

    def observe(self, consec, bad) -> None:
        """Record one guarded step's outputs (device scalars; no sync)."""
        self.consec = consec
        self._bads.append(bad)

    def observe_scan(self, bads, consec) -> None:
        """Record a whole guarded scan-epoch's outputs: the per-step bad
        flags [B] and the carry's final consecutive-bad counter (device
        arrays; no sync — same discipline as :meth:`observe`)."""
        self.consec = consec
        self._bads.append(bads.sum())

    def epoch_finalize(self) -> Tuple[int, int]:
        """One host sync per epoch: returns (skipped_this_epoch,
        consecutive_bad_at_epoch_end) and keeps them for
        :meth:`take_finalized`. The epoch's train phase calls it last,
        under the span ``train.sentry`` (``train/loop.py``)."""
        import jax
        import jax.numpy as jnp

        if self._bads:
            skipped = int(jax.device_get(jnp.stack(self._bads).sum()))
        else:
            skipped = 0
        consec_end = int(jax.device_get(self.consec))
        self.skipped_total += skipped
        self._bads = []
        self._finalized = (skipped, consec_end)
        return skipped, consec_end

    def take_finalized(self) -> Tuple[int, int]:
        """The epoch's (skipped, consecutive bad at its end): what
        :meth:`epoch_finalize` read, or read now where no train phase did."""
        out = self._finalized if self._finalized is not None else self.epoch_finalize()
        self._finalized = None
        return out

    def needs_rollback(self, consec_end: int) -> bool:
        return consec_end >= self.patience

    def on_rollback(self) -> None:
        import jax.numpy as jnp

        self.rollbacks += 1
        self.consec = jnp.zeros((), jnp.int32)

    @property
    def exhausted(self) -> bool:
        return self.rollbacks >= self.max_rollbacks
