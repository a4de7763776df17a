from repro.data.tpch import generate_tpch

__all__ = ["generate_tpch"]
