from repro.utils.hashing import rendezvous_owner
from repro.utils.logging import get_logger

__all__ = ["get_logger", "rendezvous_owner"]
