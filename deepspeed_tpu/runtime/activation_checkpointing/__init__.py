from .checkpointing import (CheckpointConfig, checkpoint, configure,
                            remat_policy)

__all__ = ["checkpoint", "configure", "CheckpointConfig", "remat_policy"]
