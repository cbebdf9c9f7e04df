"""NETTY is this package's TCP transport under the reference's name for it:
``tcp.py`` registers both, and ``TransportFactory.get("NETTY")`` finds it
here."""

from ratis_tpu.transport import tcp  # noqa: F401
