"""Timers (reference: `deepspeed/utils/timer.py`).

`SynchronizedWallClockTimer` fences XLA's async dispatch with
`jax.block_until_ready`/`jax.effects_barrier` where the reference used
`cuda.synchronize()`. `ThroughputTimer` reports samples/sec with warmup
skip; the training engine feeds it each step's dispatch to dispatch
interval (`stop(duration=...)`), so the rate is one of training, not of
enqueueing.
"""

import contextlib
import time

import psutil

import jax

from .logging import logger


def _device_barrier():
    """Drain outstanding async device work so wall-clock is meaningful."""
    try:
        jax.effects_barrier()
    except Exception:
        pass


class SynchronizedWallClockTimer:
    """Named timer group with device-synchronized start/stop."""

    class Timer:
        # time.monotonic, not time.time: an NTP slew or wall-clock jump
        # mid-span corrupts elapsed (negative or hours-long "steps" have
        # been observed on preemptible fleets); monotonic can't go back.
        def __init__(self, name):
            self.name_ = name
            self.elapsed_ = 0.0
            self.started_ = False
            self.start_time = time.monotonic()

        def start(self):
            assert not self.started_, f"{self.name_} timer already started"
            _device_barrier()
            self.start_time = time.monotonic()
            self.started_ = True

        def stop(self, reset=False):
            assert self.started_, f"{self.name_} timer not started"
            _device_barrier()
            if reset:
                self.elapsed_ = time.monotonic() - self.start_time
            else:
                self.elapsed_ += time.monotonic() - self.start_time
            self.started_ = False

        def reset(self):
            self.elapsed_ = 0.0
            self.started_ = False

        def elapsed(self, reset=True):
            started = self.started_
            if started:
                self.stop()
            elapsed = self.elapsed_
            if reset:
                self.reset()
            if started:
                self.start()
            return elapsed

    def __init__(self):
        self.timers = {}

    def __call__(self, name):
        if name not in self.timers:
            self.timers[name] = self.Timer(name)
        return self.timers[name]

    @staticmethod
    def memory_usage():
        try:
            stats = jax.local_devices()[0].memory_stats() or {}
            alloc = stats.get("bytes_in_use", 0) / 2 ** 30
            peak = stats.get("peak_bytes_in_use", 0) / 2 ** 30
            return f"hbm in-use: {alloc:.2f} GB, peak: {peak:.2f} GB"
        except Exception:
            return "hbm stats unavailable"

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name not in self.timers:
                continue
            elapsed = self.timers[name].elapsed(reset=reset) * 1000.0 / \
                normalizer
            string += f" | {name}: {elapsed:.2f}"
        if memory_breakdown:
            string += f" | {self.memory_usage()}"
        logger.info(string)


class ThroughputTimer:
    """Samples/sec with configurable warmup skip (reference
    `timer.py:105`)."""

    def __init__(self, batch_size, num_workers=1, start_step=2,
                 steps_per_output=50, monitor_memory=False, logging_fn=None):
        self.start_time = 0
        self.end_time = 0
        self.started = False
        self.batch_size = max(1, batch_size)
        self.num_workers = num_workers
        self.start_step = start_step
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or logger.info
        self.initialized = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def _init_timer(self):
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.global_step_count >= self.start_step:
            _device_barrier()
            self.start_time = time.monotonic()

    def stop(self, report_speed=True, duration=None):
        """Count one step. `duration`: its seconds where the caller has
        measured them. The engine hands in its step timeline's dispatch
        to dispatch interval: a clock around one `train_batch` call
        times the enqueue of an asynchronous step, not the step
        (`effects_barrier` waits for no computation)."""
        if not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        self.global_step_count += 1
        if self.start_time > 0:
            if duration is None:
                _device_barrier()
                self.end_time = time.monotonic()
                duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            if report_speed and \
                    self.global_step_count % self.steps_per_output == 0:
                avg = self.avg_samples_per_sec()
                if avg > 0:   # still in warmup: nothing meaningful yet
                    self.logging(
                        f"{self.global_step_count}/"
                        f"{self.micro_step_count}, "
                        f"SamplesPerSec={avg:.2f}")
                if self.monitor_memory:
                    vm = psutil.virtual_memory()
                    self.logging(f"virtual memory used: "
                                 f"{vm.used / 2**30:.2f} GB, "
                                 f"percent: {vm.percent}%")

    def avg_samples_per_sec(self):
        # 0.0 (not -inf) before warmup completes: callers feed this into
        # logs and monitor scalars, and a -inf both reads as garbage and
        # poisons downstream aggregation.
        if self.global_step_count > self.start_step and \
                self.total_elapsed_time > 0:
            samples = self.batch_size * self.num_workers
            total_step_offset = self.global_step_count - self.start_step
            avg_time_per_step = self.total_elapsed_time / total_step_offset
            return samples / avg_time_per_step
        return 0.0


@contextlib.contextmanager
def profiler_trace(logdir, create_perfetto_trace=False):
    """XProf/TensorBoard trace of everything dispatched inside the block
    (the TPU-native face of the reference's `wall_clock_breakdown` CUDA
    timers, SURVEY §5.1): per-kernel device timelines, HLO cost
    attribution, host/device overlap.

    with profiler_trace("/tmp/trace"):
        engine.train_batch(batch=...)
    # then: tensorboard --logdir /tmp/trace (or xprof)
    """
    import jax

    jax.profiler.start_trace(
        logdir, create_perfetto_trace=create_perfetto_trace)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
