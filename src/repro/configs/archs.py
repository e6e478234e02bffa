"""Import side-effect module: registers every architecture config."""
# the 10 assigned architectures
from repro.configs import jamba_v0_1_52b  # noqa: F401
from repro.configs import internvl2_76b  # noqa: F401
from repro.configs import mamba2_2_7b  # noqa: F401
from repro.configs import chatglm3_6b  # noqa: F401
from repro.configs import qwen3_32b  # noqa: F401
from repro.configs import gemma2_2b  # noqa: F401
from repro.configs import qwen2_1_5b  # noqa: F401
from repro.configs import deepseek_v2_236b  # noqa: F401
from repro.configs import qwen3_moe_30b_a3b  # noqa: F401
from repro.configs import whisper_small  # noqa: F401

# benchmark configurations beyond the assigned set
from repro.configs import deepseek_v2_lite  # noqa: F401

# the paper's own evaluation models
from repro.configs import llama3_1_8b  # noqa: F401
from repro.configs import llama3_1_70b  # noqa: F401

ASSIGNED = (
    "jamba-v0.1-52b",
    "internvl2-76b",
    "mamba2-2.7b",
    "chatglm3-6b",
    "qwen3-32b",
    "gemma2-2b",
    "qwen2-1.5b",
    "deepseek-v2-236b",
    "qwen3-moe-30b-a3b",
    "whisper-small",
)

PAPER_MODELS = ("llama3.1-8b", "llama3.1-70b")
