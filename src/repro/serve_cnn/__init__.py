"""SLO-governed continuous-batching CNN inference over BinArrayPrograms.

The serving tier for the programs the repo is about: bounded admission,
per-request deadlines, dynamic batch assembly into ``deploy.execute``, and
the paper's §IV-D runtime accuracy↔throughput switch operated *as the
degradation policy* — under latency pressure the service serves fewer
binary levels before it sheds requests, and recovers to full-M when the
pressure clears.  See docs/serving_cnn.md.
"""
from repro.serve_cnn.service import (CNNService, ImageRequest,
                                     NonFiniteOutput, SHED_REASONS, STAGES)
from repro.serve_cnn.slo import (SLOConfig, SLOController, default_ladder,
                                 schedule_cost)

__all__ = [
    "CNNService", "ImageRequest", "NonFiniteOutput", "SHED_REASONS",
    "STAGES", "SLOConfig", "SLOController", "default_ladder", "schedule_cost",
]
