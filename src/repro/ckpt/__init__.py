from .serialize import serialize_tree, deserialize_tree, Manifest
from .store import ClusterTopology, BlockStore, DiskBlockStore, NodeFailure
from .stripe import RecoveryStats, RepairReport, StripeCodec, choose_code
from .manager import CheckpointManager, RestoreReport
from repro.io.engine import CorruptionError

__all__ = ["serialize_tree", "deserialize_tree", "Manifest",
           "ClusterTopology", "BlockStore", "DiskBlockStore", "NodeFailure",
           "RecoveryStats", "RepairReport", "StripeCodec", "choose_code",
           "CheckpointManager", "RestoreReport", "CorruptionError"]
