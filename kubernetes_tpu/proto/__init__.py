"""The dense-snapshot proto boundary (SURVEY §2.6's Go↔JAX shim).

snapshot.proto is the contract; snapshot_pb2 is the committed generated
code.  Nothing regenerates it behind the caller's back: after editing
the contract run `make proto`."""

from . import snapshot_pb2  # noqa: F401
