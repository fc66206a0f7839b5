from euler_tpu.models.embedding_models import (  # noqa: F401
    SkipGramModel,
    deepwalk_batches,
    line_batches,
)
from euler_tpu.models.graphsage import (  # noqa: F401
    GraphSAGESupervised,
    GraphSAGEUnsupervised,
)
from euler_tpu.models.graph_clf import GraphClassifier  # noqa: F401
from euler_tpu.models.kg import (  # noqa: F401
    TransX,
    kg_batches,
    kg_rank_eval,
    kg_ranking_metrics,
    transx_warm_start,
)
from euler_tpu.models.layerwise_models import LayerwiseGCN  # noqa: F401
from euler_tpu.models.rgcn import RGCNSupervised  # noqa: F401
from euler_tpu.models.autoencoders import DGI, GAE, dgi_batches, gae_batches  # noqa: F401
from euler_tpu.models.sequence_lm import (  # noqa: F401
    KeyeVL2LM,
    Lfm2MoeLM,
    Qwen3NextLM,
    SmallThinkerLM,
    TrinityLM,
)
from euler_tpu.models.scalable import ScalableGNN, ScalableTrainer  # noqa: F401
