"""Model zoo (analog of paddle.vision.models + the GPT/ERNIE workloads in
BASELINE.json; the reference ships the transformer stack at
python/paddle/nn/layer/transformer.py and vision models under
python/paddle/vision/models/)."""

from .gpt import (GPT_CONFIGS, GPTForCausalLM, GPTModel, gpt2_medium,
                  gpt2_small, gpt2_tiny)
from .laguna import LAGUNA_CONFIGS, LagunaConfig, LagunaForCausalLM
from .mellum import MELLUM_CONFIGS, MellumConfig, MellumForCausalLM
from .jamba import JAMBA_CONFIGS, JambaConfig, JambaForCausalLM
from .lfm2 import LFM2_CONFIGS, Lfm2Config, Lfm2ForCausalLM
from .keye import KEYE_CONFIGS, KeyeConfig, KeyeForCausalLM
from .dotsvlm import (DOTSVLM_CONFIGS, DotsVlmConfig,
                      DotsVlmForCausalLM)
from .qwen3next import (QWEN3NEXT_CONFIGS, Qwen3NextConfig,
                        Qwen3NextForCausalLM)
from . import generation
from .generation import (beam_search, decode_step, decode_step_paged,
                         draft_ngram, greedy_search, sample,
                         verify_step_paged)
from .ernie import (ERNIE_CONFIGS, ErnieForPretraining,
                    ErnieForSequenceClassification, ErnieModel,
                    ernie_tiny)
from .ctr import DeepFM, WideDeep
