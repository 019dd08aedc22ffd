"""Architecture registry: import every config module to register it."""
from repro.configs.base import (  # noqa: F401
    ModelConfig, ShapeConfig, ParallelConfig, SHAPES,
    get_config, list_configs, applicable_shapes, reduced,
)

# assigned architectures (one module per arch, per the assignment)
from repro.configs import llama3_2_1b    # noqa: F401
from repro.configs import internlm2_1_8b # noqa: F401
from repro.configs import yi_34b         # noqa: F401
from repro.configs import gemma3_27b     # noqa: F401
from repro.configs import xlstm_350m     # noqa: F401
from repro.configs import whisper_small  # noqa: F401
from repro.configs import mixtral_8x22b  # noqa: F401
from repro.configs import phi3_5_moe     # noqa: F401
from repro.configs import qwen2_vl_7b    # noqa: F401
from repro.configs import zamba2_1_2b    # noqa: F401
# the paper's own evaluation models
from repro.configs import qwen1_5_7b     # noqa: F401
from repro.configs import qwen1_5_14b    # noqa: F401
from repro.configs import qwen1_5_72b    # noqa: F401
# served as one chip's share of an expert-parallel deployment
from repro.configs import mellum2_12b_a2_5b  # noqa: F401

ASSIGNED = (
    "llama3.2-1b", "internlm2-1.8b", "yi-34b", "gemma3-27b", "xlstm-350m",
    "whisper-small", "mixtral-8x22b", "phi3.5-moe-42b-a6.6b", "qwen2-vl-7b",
    "zamba2-1.2b",
)
PAPER_MODELS = ("qwen1.5-7b", "qwen1.5-14b", "qwen1.5-72b")
