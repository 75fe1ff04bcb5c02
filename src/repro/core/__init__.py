"""Paper core: Block Coordinate Descent for Network Linearization."""
from . import masks, linearize, bcd, engine, snl, autorep, pi_cost, analysis  # noqa

from .bcd import BCDConfig, run_bcd            # noqa: F401
from .engine import (CandidateEvaluator, SequentialEvaluator,  # noqa: F401
                     PipelinedEvaluator, SuffixEvaluator)
from .snl import SNLConfig, run_snl, finetune  # noqa: F401
from .autorep import AutoRepConfig, run_autorep  # noqa: F401
