"""Backend-dependent defaults shared by the Pallas kernels."""
