"""Model zoo: the Llama family, the MLA + sparse-expert family, the window +
full attention sparse-expert family, the learned-sparse-attention expert
family, the state-space + attention + ungated-expert family, the windowed
exact + pooled-pair attention family, the delta-rule + latent-attention
group-routed expert family, the compressed-latent convolved attention +
top-1 expert family, ResNet, MLP."""

from ray_tpu.models.cca_moe import (  # noqa: F401
    CcaMoeConfig, cca_moe_forward, cca_moe_init)
from ray_tpu.models.cohere2_moe import (  # noqa: F401
    Cohere2MoeConfig, cohere2_moe_forward, cohere2_moe_init)
from ray_tpu.models.eva import EvaConfig, eva_forward, eva_init  # noqa: F401
from ray_tpu.models.kda_moe import (  # noqa: F401
    KdaMoeConfig, kda_moe_forward, kda_moe_init)
from ray_tpu.models.llama import LlamaConfig, llama_forward, llama_init  # noqa: F401
from ray_tpu.models.mla_moe import (  # noqa: F401
    MlaMoeConfig, mla_moe_forward, mla_moe_init)
from ray_tpu.models.sparse_moe import (  # noqa: F401
    SparseMoeConfig, sparse_moe_forward, sparse_moe_init)
from ray_tpu.models.ssm_moe import (  # noqa: F401
    SsmMoeConfig, ssm_moe_forward, ssm_moe_init)

