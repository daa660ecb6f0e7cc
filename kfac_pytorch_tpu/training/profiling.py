"""Trace capture: one flag profiles any training epoch.

The reference ships NO tracing/profiling subsystem — only wall-clock totals
and tqdm postfixes (SURVEY.md §5). Here ``--profile-epoch N`` on the example
CLIs wraps that epoch in a ``jax.profiler`` trace (XLA/TPU timeline, HLO op
costs, host/device overlap), viewable in TensorBoard or Perfetto, and prints
the device time of each step program by phase (capture / refresh / apply /
optimizer / model) when the epoch ends.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional


@contextlib.contextmanager
def maybe_trace(log_dir: Optional[str], enabled: bool) -> Iterator[None]:
    """Capture a profiler trace into ``log_dir`` when ``enabled``, and print
    the device time of each step program by phase when the epoch ends
    (observability/device_phases.py; nothing to print off the TPU).

    No-op (zero overhead) otherwise; degrades to a no-op with a warning if
    the profiler backend is unavailable on this platform.
    """
    if not (enabled and log_dir):
        yield
        return
    import jax

    options = jax.profiler.ProfileOptions()
    # The host's spans are TraceAnnotations (telemetry.Span enters one);
    # Python's own frames are not traced. Host level 1 keeps the annotations
    # and drops the runtime's fine events: at the default level an ImageNet
    # epoch's batch transposes alone wrote 5.6 million host events for 30
    # steps and took the host past 40 GiB (PERF.md, PR 24).
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    try:
        jax.profiler.start_trace(log_dir, profiler_options=options)
    except Exception as e:  # profiler unavailable — don't kill training
        print(f"WARNING: profiler trace unavailable: {e}")
        yield
        return
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        from kfac_pytorch_tpu.observability import device_phases

        try:
            print(device_phases.report(log_dir))
        except (OSError, ValueError) as e:  # no trace file, or one this reader cannot parse
            print(f"WARNING: no device time by phase from {log_dir}: {e}")
