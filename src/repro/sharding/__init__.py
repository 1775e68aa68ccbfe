from .rules import (MeshCtx, set_mesh_ctx, get_mesh_ctx, shard,
                    logical_to_spec)

__all__ = ["MeshCtx", "set_mesh_ctx", "get_mesh_ctx", "shard", "logical_to_spec"]
