"""repro: Flex-SFU (non-uniform PWL activation approximation) on TPU/JAX."""

__version__ = "0.1.0"
