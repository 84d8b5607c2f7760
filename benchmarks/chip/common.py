"""What the harness hands a driver, and helpers the drivers share."""
from __future__ import annotations

import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, List, Optional

import jax


@dataclass
class Context:
    workload: str
    config: dict              # the configuration file, as run
    traffic: dict             # the traffic file
    seed: int
    devices: List[Any]        # the chips this cell uses
    trace_dir: Optional[Path] = None
    t_start: float = field(default_factory=time.perf_counter)

    def start_trace(self) -> None:
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.trace_dir))

    def stop_trace(self) -> None:
        jax.profiler.stop_trace()


def module_name(compiled) -> str:
    """The HLO module name of a compiled program: the name its runs carry
    on the profiler's "XLA Modules" line."""
    head = compiled.as_text().split("\n", 1)[0]
    if not head.startswith("HloModule "):
        raise ValueError(f"unexpected HLO header {head[:80]!r}")
    return head[len("HloModule "):].split(",", 1)[0].strip()


def check_sizes(cfg, want: dict) -> None:
    """The program's config must have the configuration file's sizes."""
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"program config differs from the configuration "
                         f"file (program, file): {bad}")


def span(name: str):
    return jax.profiler.TraceAnnotation(name)
