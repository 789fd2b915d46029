"""Trainers: BaseTrainer + DataParallelTrainer (JaxTrainer).

Reference: `python/ray/train/base_trainer.py:567` (`BaseTrainer.fit` wraps
the trainer as a Tune Trainable and runs a one-trial Tuner — Train runs ON
TOP of Tune) and `python/ray/train/data_parallel_trainer.py:25,428`
(`DataParallelTrainer.training_loop` drives the BackendExecutor).

This implementation keeps the same layering: `fit()` constructs a
single-trial `ray_tpu.tune.Tuner` when Tune is importable, falling back to
driving the controller loop inline. The controller loop itself
(`_run_training_loop`) is what the reference runs inside the Trainable
actor.
"""

from __future__ import annotations

import os
import uuid
from typing import Any, Callable, Dict, Optional

from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import (
    CheckpointConfig,
    FailureConfig,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.air.result import Result
from ray_tpu.train._internal.backend_executor import (
    BackendExecutor,
    TrainingFailedError,
)
from ray_tpu.train._internal.checkpoint_manager import (
    CheckpointManager,
    IncompleteCheckpointError,
)
from ray_tpu.train.backend import BackendConfig, JaxConfig


class TrainStepRunner:
    """Dispatch-amortized step driver for ``train_loop_per_worker``
    bodies (ROADMAP r5 #3: sub-2 ms driver dispatch).

    Wraps a pure ``step_fn(carry, batch) -> (carry, aux)`` with the AOT
    executable cache (``ray_tpu.parallel.compiled_step``): the step is
    lowered and compiled ONCE per abstract signature with the carry
    donated, so the steady-state per-step driver cost is a single
    executable dispatch — no jit-layer cache probe, no retrace risk
    (shape drift trips the retrace guard instead of silently
    recompiling).

    With ``steps_per_call=K`` (opt-in), K steps fold into ONE dispatch:
    ``run(carry, batch_iter)`` prefetches K batches on device, stacks
    them on a leading axis, and executes a single ``lax.scan``-staged
    program (``ray_tpu.parallel.fold_steps``), amortizing the fixed
    dispatch overhead K-fold. The aux stream comes back stacked
    ([K, ...]) so loss trajectories are identical to K single steps.

    Example::

        def loop(config):
            runner = train.TrainStepRunner(step, steps_per_call=8)
            for _ in range(num_reports):
                carry, losses = runner.run(carry, batch_iter)
                train.report({"loss": float(losses[-1])})
    """

    def __init__(self, step_fn: Callable, *, steps_per_call: int = 1,
                 donate_carry: bool = True, mesh=None,
                 on_retrace: str = "warn",
                 tokens_per_step: int = 0,
                 flops_per_step: float = 0.0,
                 peak_flops: Optional[float] = None):
        from ray_tpu.parallel.compile_cache import (compiled_step,
                                                    fold_steps)
        from ray_tpu.util import tracing

        if steps_per_call < 1:
            raise ValueError("steps_per_call must be >= 1")
        self.step_fn = step_fn
        self.steps_per_call = steps_per_call
        # flight recorder: optional model accounting for the per-step
        # MFU column (tokens/flops consumed PER SINGLE STEP; peak_flops
        # overrides device detection — required for MFU on CPU)
        self._tokens_per_step = tokens_per_step
        self._flops_per_step = flops_per_step
        self._peak_flops = peak_flops
        self._step = 0
        # self time by phase of run(), while the flight recorder is on
        self.phases = tracing.PhaseTable(
            ("train_step", "train_data_wait", "train_dispatch",
             "train_device_wait"))
        if steps_per_call == 1:
            self._compiled = compiled_step(
                step_fn, donate_argnums=(0,) if donate_carry else (),
                mesh=mesh, on_retrace=on_retrace)
        else:
            self._compiled = fold_steps(
                step_fn, steps_per_call, donate_carry=donate_carry,
                mesh=mesh, on_retrace=on_retrace)

    def _prep_batches(self, batches):
        from ray_tpu.parallel.compile_cache import stack_batches

        if self.steps_per_call == 1:
            if hasattr(batches, "__next__"):
                batches = next(batches)
            return batches
        if hasattr(batches, "__next__") or (
                isinstance(batches, (list, tuple))):
            it = iter(batches)
            batches = stack_batches(
                next(it) for _ in range(self.steps_per_call))
        return batches

    def run(self, carry, batches):
        """Advance ``steps_per_call`` steps in one dispatch.

        ``batches``: an iterator/iterable of per-step batches (the next
        K are pulled and stacked), or an already-stacked [K, ...] pytree
        when ``steps_per_call > 1``. Returns ``(carry, aux)`` with aux
        stacked over the K steps (a bare aux for K == 1).

        Every dispatch lands one ``StepStats`` record in the flight
        recorder (``ray_tpu.util.step_profiler``): data-wait (batch
        pull + stack), host-dispatch (time in the cached-executable
        call), and — when ``RAY_TPU_PROFILE_SYNC`` is on, the default —
        device-execute as the block-until-ready delta. Disable the
        recorder wholesale with ``RAY_TPU_STEP_PROFILER=0``."""
        from ray_tpu.util import step_profiler

        if not step_profiler.enabled():
            return self._compiled(carry, self._prep_batches(batches))
        import jax

        phase = self.phases.phase
        k = self.steps_per_call
        # a step of a profiler's overview (`rt/train_step`) when a
        # session is on, numbered by the last step it advances to
        with phase("train_step", step=self._step + k) as whole:
            with phase("train_data_wait") as data_wait:
                batches = self._prep_batches(batches)
            with phase("train_dispatch") as dispatch:
                out = self._compiled(carry, batches)
            device_ns = 0
            if step_profiler.sync_mode():
                with phase("train_device_wait") as device_wait:
                    jax.block_until_ready(out)
                device_ns = device_wait.ns
        self._step += k
        step_profiler.record_step(
            self._step, whole.ns / 1e6,
            host_dispatch_ms=dispatch.ns / 1e6,
            device_execute_ms=device_ns / 1e6,
            data_wait_ms=data_wait.ns / 1e6,
            tokens=self._tokens_per_step * k,
            flops=self._flops_per_step * k,
            steps_per_call=k,
            peak=self._peak_flops,
        )
        return out

    def cache_stats(self):
        return self._compiled.cache.stats.as_dict()

    def step_stats(self, n: Optional[int] = None):
        """The flight recorder's recent StepStats rows (dicts)."""
        from ray_tpu.util import step_profiler

        return step_profiler.recent(n)


class BaseTrainer:
    def __init__(
        self,
        *,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
        metadata: Optional[Dict[str, Any]] = None,
    ):
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.resume_from_checkpoint = resume_from_checkpoint
        self.metadata = metadata or {}

    def training_loop(self) -> None:
        raise NotImplementedError

    def fit(self) -> Result:
        """Run via Tune when available (reference layering), else inline.

        Failures surface as exceptions, not silently as ``Result.error``
        (reference `BaseTrainer.fit` raises TrainingFailedError,
        `base_trainer.py:567`).
        """
        from ray_tpu.util import tracing

        tracing.startup_mark("deploy_call", {"entry": "JaxTrainer.fit"})
        try:
            from ray_tpu.tune.tuner import Tuner
        except ImportError:
            return self._fit_inline()
        tuner = Tuner(
            self.as_trainable(),
            run_config=self.run_config,
        )
        grid = tuner.fit()
        result = grid[0]
        if result.error is not None:
            if isinstance(result.error, TrainingFailedError):
                raise result.error
            raise TrainingFailedError(str(result.error)) from result.error
        return result

    def as_trainable(self):
        """Wrap as a Tune trainable function (reference
        `BaseTrainer.as_trainable`, `base_trainer.py:760`)."""
        from ray_tpu.tune import trainable as trainable_mod
        trainer = self

        def train_func(config):
            from ray_tpu.tune import trainable as t_mod
            # On a Tune-side trial restart the session carries the restore
            # checkpoint; it supersedes the original resume_from_checkpoint.
            sess = t_mod.session_mod.get_session()
            if sess is not None and sess.get_checkpoint() is not None:
                trainer.resume_from_checkpoint = sess.get_checkpoint()
            trainer._run_training_loop(report_fn=t_mod.session_report)

        train_func.__name__ = type(self).__name__
        tr = trainable_mod.wrap_function(train_func)
        # Trial actors must reserve the whole worker fleet's resources via
        # their own PG; trial resources = trainer bundle only (workers make
        # their own PG) — matches reference PlacementGroupFactory shape.
        tr._trainer_resources = self.scaling_config.trainer_resources or \
            {"CPU": 1.0}
        return tr

    def _fit_inline(self) -> Result:
        out: Dict[str, Any] = {}

        def collect(metrics, checkpoint=None):
            out["metrics"] = metrics
            if checkpoint is not None:
                out["checkpoint"] = checkpoint

        self._run_training_loop(report_fn=collect)
        return Result(metrics=out.get("metrics"),
                      checkpoint=out.get("checkpoint"),
                      path=self._trial_dir)

    # subclasses implement
    def _run_training_loop(self, report_fn: Optional[Callable]) -> None:
        raise NotImplementedError


class DataParallelTrainer(BaseTrainer):
    """SPMD trainer: N identical workers, one jax process each.

    Reference: `python/ray/train/data_parallel_trainer.py:25`. The "data
    parallel" here is about the *worker fleet*; within and across workers
    the model may still be sharded DP/FSDP/TP/SP via the mesh the train
    loop builds (ray_tpu.parallel) — the trainer provides the gang +
    rendezvous + report plumbing.
    """

    _backend_config_cls = BackendConfig

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        backend_config: Optional[BackendConfig] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
        metadata: Optional[Dict[str, Any]] = None,
    ):
        super().__init__(scaling_config=scaling_config,
                         run_config=run_config,
                         resume_from_checkpoint=resume_from_checkpoint,
                         metadata=metadata)
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.backend_config = backend_config or self._backend_config_cls()
        self.datasets = datasets or {}
        self._trial_dir: Optional[str] = None

    def _run_training_loop(self, report_fn: Optional[Callable]) -> None:
        """The controller loop (runs in the Trainable actor under Tune,
        or inline in the driver)."""
        name = self.run_config.name or f"{type(self).__name__}_" \
            f"{uuid.uuid4().hex[:8]}"
        trial_id = uuid.uuid4().hex[:8]
        executor = BackendExecutor(
            self.backend_config, self.scaling_config,
            experiment_name=name,
            storage_path=self.run_config.storage_path,
            trial_id=trial_id,
        )
        self._trial_dir = os.path.join(self.run_config.storage_path, name,
                                       trial_id)
        ckpt_manager = CheckpointManager(self.run_config.checkpoint_config)
        max_failures = self.run_config.failure_config.max_failures
        attempts = 0
        restore_checkpoint = self.resume_from_checkpoint
        while True:
            try:
                executor.start()
                executor.start_training(
                    self.train_loop_per_worker,
                    config=self.train_loop_config,
                    datasets=self.datasets,
                    checkpoint=restore_checkpoint,
                )
                last_metrics: Optional[Dict[str, Any]] = None
                while True:
                    results = executor.get_next_results()
                    if results is None:
                        break
                    # Lowest live world rank speaks for the step; its
                    # checkpoint is the canonical (rank-0) one only while
                    # rank 0 is still reporting.
                    lead = min(results, key=lambda r: r["world_rank"])
                    last_metrics = lead["metrics"]
                    checkpoint = None
                    if lead.get("checkpoint_path") and \
                            lead["world_rank"] == 0:
                        checkpoint = Checkpoint(lead["checkpoint_path"])
                        # Already in trial storage: the Tune session must
                        # reference it, not re-copy it (a second persisted
                        # copy would double disk use and escape the
                        # CheckpointManager's num_to_keep eviction).
                        checkpoint._persisted = True
                        try:
                            ckpt_manager.register_checkpoint(
                                checkpoint, last_metrics,
                                require_usable=True)
                        except IncompleteCheckpointError as e:
                            raise TrainingFailedError(str(e)) from e
                    # Gang-durable commit: the checkpoint is registered;
                    # release every rank blocked in report()'s barrier.
                    # Unconditional — when rank 0 has already finished,
                    # later ranks' checkpoint reports still hold the
                    # barrier and must be released even though nothing
                    # was registered for them.
                    executor.commit_gang_checkpoint()
                    if report_fn is not None:
                        report_fn(last_metrics, checkpoint=checkpoint)
                executor.shutdown()
                return
            except TrainingFailedError:
                executor.shutdown()
                attempts += 1
                if max_failures >= 0 and attempts > max_failures:
                    raise
                restore_checkpoint = self._latest_usable_checkpoint(
                    ckpt_manager) or restore_checkpoint
            except BaseException:
                executor.shutdown()
                raise


    @staticmethod
    def _latest_usable_checkpoint(ckpt_manager: CheckpointManager):
        """Newest checkpoint whose shard set is complete. A gang killed
        mid-persist can leave a sharded checkpoint missing some ranks'
        files; restoring from it would fail again, so the restart walks
        back to the newest complete one (dict checkpoints are atomic and
        always usable)."""
        from ray_tpu.train import array_checkpoint

        for ckpt, _metrics in reversed(ckpt_manager.best_checkpoints()):
            if array_checkpoint.is_usable(ckpt):
                return ckpt
        return None


class JaxTrainer(DataParallelTrainer):
    """The flagship trainer: jax.distributed + mesh-parallel training.

    Reference analogue: `TorchTrainer` (`python/ray/train/torch/
    torch_trainer.py`) — with `JaxConfig` replacing `TorchConfig`
    (NCCL → XLA/ICI collectives; see `ray_tpu/train/backend.py`).
    """

    _backend_config_cls = JaxConfig
