"""repro — LOG.io (unified rollback recovery + data lineage) feeding a JAX
train step on one device. See README.md."""
__version__ = "1.0.0"
