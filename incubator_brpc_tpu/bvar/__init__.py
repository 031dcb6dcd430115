"""bvar — write-mostly, thread-locally aggregated metrics (reference src/bvar/).

Design reproduced from the reference (SURVEY.md §5): the *write* path touches
only a per-thread agent (no shared cache-line bouncing — reference
``detail/agent_group.h``); the *read* path combines all agents
(``detail/combiner.h``). Types: Adder/Maxer/Miner (reducer.h:67,223),
IntRecorder, LatencyRecorder (latency percentiles + qps over windows,
latency_recorder.h), PassiveStatus, Window/PerSecond backed by a 1 Hz sampler
thread (detail/sampler.cpp), and a global expose/dump registry
(variable.h:97-204) served by the /vars builtin service.
"""

from incubator_brpc_tpu.bvar.variable import Variable, expose_registry, dump_exposed
from incubator_brpc_tpu.bvar.reducer import Adder, Maxer, Miner, PassiveStatus
from incubator_brpc_tpu.bvar.recorder import (
    IntRecorder,
    LatencyRecorder,
    RecorderFeed,
    feeds,
)
from incubator_brpc_tpu.bvar.ring import CPU_CLOCK_EVERY, Ring, clocks
from incubator_brpc_tpu.bvar.window import Window, PerSecond, sampler_passes
from incubator_brpc_tpu.bvar.percentile import Percentile

__all__ = [
    "Variable",
    "expose_registry",
    "dump_exposed",
    "Adder",
    "Maxer",
    "Miner",
    "PassiveStatus",
    "IntRecorder",
    "LatencyRecorder",
    "RecorderFeed",
    "feeds",
    "Ring",
    "CPU_CLOCK_EVERY",
    "clocks",
    "sampler_passes",
    "Window",
    "PerSecond",
    "Percentile",
]
