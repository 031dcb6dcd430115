"""models — flagship workloads of the fabric.

The reference validates itself with its example pairs (echo_c++,
streaming_echo, parallel_echo — /root/reference/example/); ours are device
workloads:

- ``tensor_echo``: the echo_c++ analog — a fully jitted echo RPC step whose
  payload lives in HBM (framing + checksum + handler + response framing).
- ``record_table``: a keyed record store whose table lives in HBM between
  calls, behind the same ``DeviceEndpoint`` (YCSB's ``usertable``; a
  parameter or embedding shard): reads gather, updates change the state the
  next dispatch reads.
- ``expert_shard``: one rank of an expert-parallel unit behind the same
  ``DeviceEndpoint``: DeepSeek-V3's routed experts, weights in HBM that a
  step reads and never replaces, the tokens a caller's router sent here
  served whatever their split over the experts; the one service whose step
  is bound on the device (a Pallas kernel over the weights where they lie),
  and the one that takes a tensor operand (``dispatch_tensor``).
- ``expert_exchange``: who calls it: a source rank's plan of a layer from the
  router's choice, ``gather`` and ``combine`` on its own chip, and a layer
  call that keeps a tensor call to each rank in flight together
  (``ExpertExchange``): the dispatch and combine of an expert-parallel unit
  over the links' lanes, no token and no answer ever in host memory.
- ``fabricnet``: the flagship multi-chip workload — a sharded MoE/pipeline
  network whose forward/backward exercises every combo-channel lowering
  (dp fan-out, tp partition, pp pipeline stream, sp ring, ep all_to_all).
"""

from incubator_brpc_tpu.models.expert_exchange import ExpertExchange
from incubator_brpc_tpu.models.expert_shard import ExpertShardService
from incubator_brpc_tpu.models.record_table import RecordTableService
from incubator_brpc_tpu.models.tensor_echo import TensorEchoService, make_echo_step
from incubator_brpc_tpu.models.fabricnet import (
    FabricNetConfig,
    init_params,
    make_train_step,
    make_forward_step,
)

__all__ = [
    "ExpertExchange",
    "ExpertShardService",
    "RecordTableService",
    "TensorEchoService",
    "make_echo_step",
    "FabricNetConfig",
    "init_params",
    "make_train_step",
    "make_forward_step",
]
