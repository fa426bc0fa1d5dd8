from dgraph_tpu.models.mlp import MLP
from dgraph_tpu.models.gcn import GraphConvLayer, GCN
from dgraph_tpu.models.sage import SAGEConv, GraphSAGE
from dgraph_tpu.models.gat import GATConv, GAT
from dgraph_tpu.models.norm import DistributedBatchNorm, RMSNorm
from dgraph_tpu.models.rgat import RGAT, RGATLayer, RelationalAttention
from dgraph_tpu.models.graph_transformer import GPSLayer, GraphTransformer
from dgraph_tpu.models.transformer import SeqTransformerLM, TransformerBlock
from dgraph_tpu.models.looplm import LoopLM, LoopLMLayer

__all__ = [
    "GPSLayer",
    "SeqTransformerLM",
    "LoopLM",
    "LoopLMLayer",
    "RMSNorm",
    "TransformerBlock",
    "GraphTransformer",
    "RGAT",
    "RGATLayer",
    "RelationalAttention",
    "MLP",
    "GraphConvLayer",
    "GCN",
    "SAGEConv",
    "GraphSAGE",
    "GATConv",
    "GAT",
    "DistributedBatchNorm",
]
