from repro.utils.tree import (
    tree_size,
    tree_bytes,
    tree_paths,
    map_with_path,
    tree_zeros_like,
    tree_cast,
    tree_global_norm,
    flatten_dict,
    unflatten_dict,
)
from repro.utils.logging import get_logger

__all__ = [
    "tree_size",
    "tree_bytes",
    "tree_paths",
    "map_with_path",
    "tree_zeros_like",
    "tree_cast",
    "tree_global_norm",
    "flatten_dict",
    "unflatten_dict",
    "get_logger",
]
