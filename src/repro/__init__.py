"""repro — QuantEase (Behdin et al., 2023) as a production JAX framework."""

__version__ = "0.1.0"
