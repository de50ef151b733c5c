"""The sizes a configuration file states, read by its family module
(``bench/models``).  The harness asks ``bench.models.family(cfg).arch``
itself; this name is kept for the program's own tests, which build an
engine from the benchmark's configuration."""

from __future__ import annotations

from typing import Dict

from bench.models import family


def arch_of(cfg: Dict):
    return family(cfg).arch(cfg)
