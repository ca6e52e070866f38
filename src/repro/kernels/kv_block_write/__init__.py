from .ops import write_kv_blocks  # noqa: F401
