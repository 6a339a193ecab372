"""repro — Distributed PCA for Wireless Sensor Networks (Le Borgne et al.)
grown into a streaming PCA server on JAX and Pallas.

Packages on the server's path: core (the paper), sensors, kernels,
streaming, serve, distributed.sharding, runtime, launch, analysis,
train.checkpoint.  The rest (models, data, train's trainer and optimizer,
distributed's pipeline and PowerSGD, the LM configs) is an LM island that
no benchmark cell runs.
"""

__version__ = "0.1.0"
