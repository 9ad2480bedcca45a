"""flowgger-tpu: a TPU-native log collector.

A from-scratch framework with the capabilities of awslabs/flowgger
(reference mounted at /root/reference): transports → framing → decode →
encode → queue → sinks, driven by the same TOML config surface, with the
hot decode path batched onto TPU via columnar JAX (jnp) programs
(``input.format = "rfc5424_tpu"`` and friends).

Public API matches the reference's single entry point
(src/lib.rs:18-20): ``flowgger_tpu.start(config_path)``.
"""

from .pipeline import start

__version__ = "0.1.0"

__all__ = ["start", "__version__"]
