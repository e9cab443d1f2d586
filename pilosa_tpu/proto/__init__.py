"""Wire types (protobuf). `from pilosa_tpu.proto import internal_pb2`.

``internal_pb2.py`` is committed generated code and is the source at run
time: importing this package never runs ``protoc``. After editing
``internal.proto`` regenerate it by hand:
``protoc --python_out=pilosa_tpu/proto -Ipilosa_tpu/proto
pilosa_tpu/proto/internal.proto``.
"""

from . import internal_pb2  # noqa: F401
