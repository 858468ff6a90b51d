from . import checkpoint, elastic, fault_tolerance, spans

__all__ = ["checkpoint", "elastic", "fault_tolerance", "spans"]
