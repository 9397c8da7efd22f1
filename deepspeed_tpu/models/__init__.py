"""Model zoo — TPU-native reference models for the driver config ladder.

The reference ships no models of its own for training (users bring torch
modules); its inference-v2 tree carries llama/mistral/mixtral implementations
(``deepspeed/inference/v2/model_implementations/`` [K]).  Here the model zoo
is first-class because the JAX engine consumes pure loss functions: each
model exposes ``init_params``, ``forward``, ``loss`` and partition-spec rules
that compose with the ZeRO sharding policy.
"""

from .bert import BertConfig, BertModel
from .exaone_moe import ExaoneMoeConfig, ExaoneMoeModel
from .falcon_h1 import FalconH1Config, FalconH1Model
from .llama import LlamaConfig, LlamaModel
from .mimo_v2 import MimoV2Config, MimoV2Model
from .mixtral import MixtralConfig, MixtralModel
from .nemotron_h import NemotronHConfig, NemotronHModel
from .olmoe import OlmoeConfig, OlmoeModel
from .opt import OPTConfig, OPTModel
from .pangu_ultra_moe import PanguUltraMoeConfig, PanguUltraMoeModel
from .resnet import ResNetConfig, ResNetModel
from .solar_open2 import SolarOpen2Config, SolarOpen2Model

__all__ = ["BertConfig", "BertModel", "ExaoneMoeConfig", "ExaoneMoeModel",
           "FalconH1Config", "FalconH1Model",
           "LlamaConfig", "LlamaModel",
           "MimoV2Config", "MimoV2Model", "MixtralConfig", "MixtralModel",
           "NemotronHConfig", "NemotronHModel", "OlmoeConfig", "OlmoeModel",
           "OPTConfig", "OPTModel",
           "PanguUltraMoeConfig", "PanguUltraMoeModel",
           "ResNetConfig", "ResNetModel",
           "SolarOpen2Config", "SolarOpen2Model"]
