"""Pluggable update compression for the FL runtimes (docs/COMPRESSION.md).

Importing this package registers the full codec zoo; ``get_codec`` is
the single entry point the server runtimes and benchmarks use.
"""
from repro.compress.base import (Codec, DeviceWire,  # noqa: F401
                                 IdentityCodec, Payload, RowDelta,
                                 get_codec, register, wire_nbytes)
from repro.compress.composed import TopKQuantCodec  # noqa: F401
from repro.compress.error_feedback import (ErrorFeedback,  # noqa: F401
                                           compress_payload,
                                           compress_update)
from repro.compress.quantize import QuantCodec  # noqa: F401
from repro.compress.sparsify import TopKCodec  # noqa: F401
