from .checkpoint import (
    latest_step,
    load_checkpoint_tree,
    restore_checkpoint,
    save_checkpoint,
)

__all__ = [
    "latest_step",
    "load_checkpoint_tree",
    "restore_checkpoint",
    "save_checkpoint",
]
