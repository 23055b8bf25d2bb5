"""Where the benchmark touches the program, all in one place.

``run_training`` is called whole; nothing of it is re-implemented. The
benchmark needs a few things the entry point does not hand out, and takes
each by wrapping a module-level name for the length of one run:

  hydragnn_tpu.api.create_train_state          weights from ``--seed`` go in
  hydragnn_tpu.train.loop.train_epoch_scan     } the start of every epoch
  hydragnn_tpu.train.loop.train_epoch          } (own clock: window, trace,
                                                 stop) and the first steps'
                                                 losses and state come out

Under the profiler it opens two annotations, the window marks
``bench_trace_begin`` / ``bench_trace_end``, and no other: the phases of
an epoch are the program's own spans (``obs/spans.py``), in the trace and
in the flight record alike. What is specific to a family of configurations
(which weights, what to count of the loader's samples for the exact
checks) it asks of ``cell.fam``.

A wrapper calls the wrapped function with the arguments it was given and
returns what it returned. The step functions themselves are wrapped only
until the first ``check_steps`` steps have been captured; after that the
loop gets the program's own objects back. The run is ended by the
program's own graceful stop: SIGTERM to this process at a window
boundary, ``TrainingPreempted`` out of ``run_training``.

If a refactor of the program moves one of these names, ``install`` fails
loudly; this file is then the only one to mend.
"""

from __future__ import annotations

import os
import signal
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np


def _adam_moments(opt_state):
    """The (mu, nu) trees inside an optax state, wherever it nests them."""
    if hasattr(opt_state, "mu") and hasattr(opt_state, "nu"):
        return opt_state.mu, opt_state.nu
    children = []
    if hasattr(opt_state, "inner_state"):
        children.append(opt_state.inner_state)
    if hasattr(opt_state, "inner_opt_state"):
        children.append(opt_state.inner_opt_state)
    if isinstance(opt_state, (tuple, list)):
        children.extend(opt_state)
    for c in children:
        found = _adam_moments(c)
        if found is not None:
            return found
    return None


def _with_zero_learning_rate(state):
    """A copy of the train state whose injected learning rate is 0: AdamW
    then moves nothing, and its moments gather every step's gradient at
    the SAME weights. A value in the state, not a shape: no recompile."""
    import jax.numpy as jnp

    def zero(opt_state):
        if hasattr(opt_state, "hyperparams"):
            hp = dict(opt_state.hyperparams)
            hp["learning_rate"] = jnp.zeros_like(hp["learning_rate"])
            return opt_state._replace(hyperparams=hp)
        if hasattr(opt_state, "inner_opt_state"):
            return opt_state._replace(inner_opt_state=zero(opt_state.inner_opt_state))
        raise AttributeError(f"no learning rate found in {type(opt_state).__name__}")

    copied = jax.tree_util.tree_map(jnp.copy, state)
    return copied.replace(opt_state=zero(copied.opt_state))


def _host_state(state) -> Dict[str, Any]:
    mu, nu = _adam_moments(state.opt_state)
    return jax.device_get({"params": state.params, "mu": mu, "nu": nu})


class Taps:
    def __init__(self, cell, seed: int, seconds: float, samples: List[Any],
                 trace_dir: Optional[str] = None):
        self.cell = cell
        self.seed = seed
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self._index_of = {id(s): i for i, s in enumerate(samples)}
        self._patched: List[tuple] = []
        # what a run leaves behind
        self.initial_params = None
        self.epoch_t: List[float] = []  # perf_counter at the start of each epoch
        self.window_first: Optional[int] = None  # first epoch of the window
        self.window_last: Optional[int] = None  # epoch whose start closes it
        self.traced: Optional[tuple] = None  # (first, last+1) epochs under the profiler
        self.train_ids: Optional[List[int]] = None
        self.sample_counts: Dict[str, Any] = {}  # what the family counted of the loader's samples
        self.marks: Dict[str, float] = {}  # perf_counter at points of the set-up
        self.step_groups: List[List[List[int]]] = []  # [step][device] -> raw sample ids
        self.losses: List[float] = []
        self.states: Dict[int, Dict[str, Any]] = {}
        self.graphs_seen: List[int] = []  # real graphs the program counted in each captured step
        self.probe: Optional[Dict[str, Any]] = None  # scanned dispatch: the lr = 0 pass (see _capturing_scan)
        self.mode: Optional[str] = None
        self.steps_per_epoch: Optional[int] = None
        self.graphs_per_epoch: Optional[int] = None
        self._steps_seen = 0
        self._stop_sent = False
        self._tracing = False
        # the window opens and closes on multiples of the checkpoint
        # interval, so that every window holds the same mix of epochs
        k = cell.ckpt_every
        self._open_at = -(-cell.warmup_epochs // k) * k

    # -- patching ----------------------------------------------------------

    def _patch(self, module, name: str, make_wrapper) -> None:
        real = getattr(module, name)  # AttributeError = the program moved it
        setattr(module, name, make_wrapper(real))
        self._patched.append((module, name, real))

    def install(self) -> "Taps":
        import hydragnn_tpu.api as api
        import hydragnn_tpu.train.loop as loop

        self._patch(api, "create_train_state", self._wrap_create_state)
        self._patch(loop, "train_epoch_scan", self._wrap_train_scan)
        self._patch(loop, "train_epoch", self._wrap_train_steps)
        return self

    def uninstall(self) -> None:
        for module, name, real in reversed(self._patched):
            setattr(module, name, real)
        self._patched.clear()
        if self._tracing:
            self._stop_trace()

    def __enter__(self):
        return self.install()

    def __exit__(self, *exc):
        self.uninstall()

    # -- weights in --------------------------------------------------------

    def _wrap_create_state(self, real):
        def create_train_state(variables, tx, *args, **kwargs):
            import weights

            self.marks["model_built"] = time.perf_counter()

            make = getattr(self.cell.fam, "weights", weights.make)
            params = make(variables["params"], self.seed)
            self.initial_params = jax.device_get(params)
            return real({**variables, "params": params}, tx, *args, **kwargs)

        return create_train_state

    # -- epochs ------------------------------------------------------------

    def _stop_trace(self) -> None:
        with jax.profiler.TraceAnnotation("bench_trace_end"):
            pass
        jax.profiler.stop_trace()
        self._tracing = False

    def _epoch_start(self) -> int:
        i = len(self.epoch_t)
        now = time.perf_counter()
        self.epoch_t.append(now)
        k = self.cell.ckpt_every
        if i == self._open_at:
            self.window_first = i
        if self.trace_dir is not None and self.traced is None and i == self._open_at + k:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the host spans are the program's own TraceAnnotations
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench_trace_begin"):
                pass
            self._tracing = True
            self._trace_from = i
        elif self._tracing and i >= self._trace_from + self.cell.trace_epochs:
            self._stop_trace()
            self.traced = (self._trace_from, i)
        if (
            self.window_first is not None
            and not self._stop_sent
            and i > self.window_first
            and (i - self.window_first) % k == 0
            and now - self.epoch_t[self.window_first] >= self.seconds
            and not self._tracing
            and (self.trace_dir is None or self.traced is not None)
        ):
            self.window_last = i
            self._stop_sent = True
            os.kill(os.getpid(), signal.SIGTERM)  # the program's own graceful stop
        return i

    def _ids(self, loader, positions) -> List[int]:
        return [self._index_of[id(loader.samples[int(p)])] for p in positions]

    def _note_loader(self, loader) -> None:
        if self.train_ids is None:
            self.train_ids = self._ids(loader, range(len(loader.samples)))
            self.sample_counts = self.cell.fam.count_samples(self.train_ids, loader.samples)
            self.steps_per_epoch = len(loader)
            self.graphs_per_epoch = len(loader.samples)

    def _wrap_train_scan(self, real):
        def train_epoch_scan(loader, state, scan_fn, epoch, *args, **kwargs):
            self._epoch_start()
            self._note_loader(loader)
            if not self.states:
                self.mode = "scan_epoch"
                scan_fn = self._capturing_scan(scan_fn, loader)
            return real(loader, state, scan_fn, epoch, *args, **kwargs)

        return train_epoch_scan

    def _capturing_scan(self, scan_fn, loader):
        def scan(state, stacked, order, *rest):
            # A scanned epoch shows its state only after its last step, by
            # when bfloat16 and float32 runs have drifted apart (Adam's
            # first steps turn rounding into full-size moves). So the same
            # compiled program first runs once on a copy of the state with
            # the learning rate at 0: every step's loss and gradient at the
            # initial weights, nothing moved. Then the real first epoch.
            pout = scan_fn(_with_zero_learning_rate(state), stacked, order, *rest)
            self.probe = {"losses": [float(x) for x in np.asarray(pout[1])], "state": _host_state(pout[0])}
            out = scan_fn(state, stacked, order, *rest)
            self.graphs_seen = [int(round(float(x))) for x in np.asarray(out[3])]
            bs = loader.batch_size
            for b in np.asarray(order):
                self.step_groups.append([self._ids(loader, range(int(b) * bs, (int(b) + 1) * bs))])
            self.losses = [float(x) for x in np.asarray(out[1])]
            self.states[len(self.losses)] = _host_state(out[0])
            return out

        return scan

    def _wrap_train_steps(self, real):
        def train_epoch(loader, state, train_step, *args, **kwargs):
            self._epoch_start()
            self._note_loader(loader)
            if self._steps_seen < self.cell.check_steps:
                self.mode = "per_step"
                train_step = self._capturing_step(train_step, loader)
            return real(loader, state, train_step, *args, **kwargs)

        return train_epoch

    def _capturing_step(self, train_step, loader):
        order = loader._order()  # this epoch's shuffle, as __iter__ will draw it
        bs, stack = loader.batch_size, loader.device_stack
        sub = bs // stack

        def step(state, batch, *rest):
            out = train_step(state, batch, *rest)
            k = self._steps_seen
            if k < self.cell.check_steps:
                chunk = order[k * bs : (k + 1) * bs]
                self.step_groups.append(
                    [self._ids(loader, chunk[d * sub : (d + 1) * sub]) for d in range(stack)]
                )
                self.losses.append(float(out[1]))
                self.graphs_seen.append(int(np.asarray(batch.graph_mask).sum()))
                if k + 1 in (1, self.cell.check_steps):
                    self.states[k + 1] = _host_state(out[0])
            self._steps_seen += 1
            return out

        return step
