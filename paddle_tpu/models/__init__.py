"""Model zoo capability surface.

Parity: the out-of-repo zoos named by BASELINE.json (PaddleClas ResNet,
PaddleNLP BERT/ERNIE + Llama, PaddleRec DeepFM, PaddleDetection PP-YOLOE).
Each family lives here as a first-class citizen of the TPU framework.
"""

from . import llama  # noqa: F401
from . import bert  # noqa: F401
from . import gpt  # noqa: F401
from . import deepfm  # noqa: F401
from . import ernie  # noqa: F401
from . import ppyoloe  # noqa: F401
from . import cohere2_moe  # noqa: F401
from . import minicpm_sala  # noqa: F401
from . import qwen3_next  # noqa: F401
from . import mellum  # noqa: F401
