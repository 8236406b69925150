"""tpu_mx.models — reference workload models (SURVEY §2.4 capability
checklist): LeNet (MNIST), model-zoo ResNets, PTB LSTM LM, BERT, SSD; and
the causal decoder blocks (decoder.py: latent attention, gated MLP,
multi-token head; experts in parallel/moe.py)."""
from .lenet import lenet
from .lstm_lm import RNNModel
from .bert import (BERTEncoder, BERTModel, bert_base_config,
                   bert_data_specs, bert_sharding_rules)
from .decoder import (CausalLM, DecoderLayer, GatedMLP, LatentAttention,
                      rotary)
from .ssd import SSD, SSDTrainingTargets, ssd_300, ssd_512
