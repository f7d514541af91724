"""Plain float32 reference of the served models (see forward.py)."""
from reference.forward import Reference, leaf_specs, served_gaps

__all__ = ["Reference", "leaf_specs", "served_gaps"]
