"""Pluggable transport SPI.

Capability parity with the reference RpcType / ServerFactory / ClientFactory
SPI (ratis-common/.../rpc/SupportedRpcType.java:24-48, RpcFactory): a server
binds one endpoint serving all its groups; clients and peer servers reach it
by peer address.  Implementations: SIMULATED (in-memory, deterministic,
fault-injectable — the test transport, cf. the reference's
SimulatedRequestReply), GRPC and TCP (real network; NETTY is TCP's other
name).
"""

from __future__ import annotations

import abc
import importlib
from typing import Awaitable, Callable, Optional

from ratis_tpu.protocol.ids import RaftPeerId
from ratis_tpu.protocol.requests import RaftClientReply, RaftClientRequest

# A server exposes these two handlers to its transport:
ServerRpcHandler = Callable[[object], Awaitable[object]]          # raftrpc msg -> reply
ClientRequestHandler = Callable[[RaftClientRequest], Awaitable[RaftClientReply]]


class ServerTransport(abc.ABC):
    """One server's endpoint: receives server RPCs + client requests, and
    sends server RPCs to peers."""

    #: True where this end hands a lane's sequenced append frames to the
    #: server in turn, each only once its predecessor's reply is ready;
    #: False where every frame that arrives is worked on beside the others.
    lane_frames_in_turn = False

    @abc.abstractmethod
    async def start(self) -> None: ...

    @abc.abstractmethod
    async def close(self) -> None: ...

    @abc.abstractmethod
    async def send_server_rpc(self, to: RaftPeerId, msg) -> object:
        """Request/response to a peer server (vote/append/snapshot/...)."""

    @property
    @abc.abstractmethod
    def address(self) -> str: ...


class ClientTransport(abc.ABC):
    """Client side: send a RaftClientRequest to a given peer."""

    @abc.abstractmethod
    async def send_request(self, peer_address: str,
                           request: RaftClientRequest) -> RaftClientReply: ...

    async def close(self) -> None:
        pass


class TransportFactory:
    """Registry keyed by rpc type string (GRPC / TCP / NETTY).  A factory
    registers itself when its module is imported, and a type nobody has
    imported yet is found by its name: ``ratis_tpu.transport.<type>``, as
    the reference's SupportedRpcType.valueOf finds its factory class."""

    _factories: dict[str, "TransportFactory"] = {}

    @classmethod
    def register(cls, rpc_type: str, factory: "TransportFactory") -> None:
        cls._factories[rpc_type.upper()] = factory

    @classmethod
    def get(cls, rpc_type: str) -> "TransportFactory":
        key = rpc_type.upper()
        if key not in cls._factories:
            try:
                importlib.import_module("ratis_tpu.transport." + key.lower())
            except ModuleNotFoundError:
                pass
        try:
            return cls._factories[key]
        except KeyError:
            raise ValueError(f"unsupported rpc type {rpc_type!r}; "
                             f"known: {sorted(cls._factories)}") from None

    def new_server_transport(self, peer_id: RaftPeerId, address: str,
                             server_handler: ServerRpcHandler,
                             client_handler: ClientRequestHandler,
                             properties=None,
                             peer_resolver=None) -> ServerTransport:
        """peer_resolver: RaftPeerId -> address | None, for transports that
        dial peers by network address (the simulated hub routes by id)."""
        raise NotImplementedError

    def new_client_transport(self, properties=None) -> ClientTransport:
        raise NotImplementedError
