from euler_tpu.dataflow.base import Block, DataFlow, MiniBatch, fanout_block  # noqa: F401
from euler_tpu.dataflow.device import (  # noqa: F401
    DeviceDgiFlow,
    DeviceEdgeFlow,
    DeviceGaeFlow,
    DeviceGraphTables,
    DeviceKGFlow,
    DeviceLayerwiseFlow,
    DeviceRelationFlow,
    DeviceSageFlow,
    DeviceSequenceFlow,
    DeviceUnsupSageFlow,
    DeviceWalkFlow,
    DeviceWholeGraphFlow,
)
from euler_tpu.dataflow.sage import FullNeighborDataFlow, SageDataFlow  # noqa: F401
from euler_tpu.dataflow.walk import gen_pair  # noqa: F401
from euler_tpu.dataflow.whole import (  # noqa: F401
    FullGraphFlow,
    GraphBatch,
    WholeGraphDataFlow,
    graph_label_batches,
)
from euler_tpu.dataflow.layerwise import LayerwiseBatch, LayerwiseDataFlow  # noqa: F401
from euler_tpu.dataflow.relation import RelationDataFlow, RelMiniBatch  # noqa: F401
