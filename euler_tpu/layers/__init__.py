from euler_tpu.layers.conv import (  # noqa: F401
    AGNNConv,
    ARMAConv,
    DNAConv,
    GatedGraphConv,
    GeniePathConv,
    RelationConv,
    APPNPConv,
    Conv,
    GATConv,
    GCNConv,
    GINConv,
    GraphConv,
    LGCNConv,
    SAGEConv,
    SGCNConv,
    TAGConv,
    degrees,
)
from euler_tpu.layers.moe import DenseMLP, SparseMoE  # noqa: F401
from euler_tpu.layers.sequence import (  # noqa: F401
    GatedAttention,
    GatedDeltaNet,
    RMSNorm,
)

CONVS = {
    "gcn": GCNConv,
    "sage": SAGEConv,
    "gat": GATConv,
    "gin": GINConv,
    "graph": GraphConv,
    "appnp": APPNPConv,
    "sgcn": SGCNConv,
    "tagcn": TAGConv,
    "agnn": AGNNConv,
    "arma": ARMAConv,
    "dna": DNAConv,
    "gated": GatedGraphConv,
    "geniepath": GeniePathConv,
    "lgcn": LGCNConv,
}


def get_conv(name: str):
    if name not in CONVS:
        raise KeyError(f"unknown conv {name!r}; have {sorted(CONVS)}")
    return CONVS[name]
