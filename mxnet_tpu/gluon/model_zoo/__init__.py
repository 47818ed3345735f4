"""Model zoo (parity: python/mxnet/gluon/model_zoo/)."""
from . import vision
from . import transformer
from .transformer import TransformerBlock, TransformerLM, transformer_lm
from . import qwen3_next
from .qwen3_next import Qwen3NextBlock, Qwen3NextLM, qwen3_next_lm
from . import trinity
from .trinity import TrinityBlock, TrinityLM, trinity_lm
from . import nemotron_h
from .nemotron_h import NemotronHBlock, NemotronHLM, nemotron_h_lm
