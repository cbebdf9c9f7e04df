"""The operation of the stream cell's warm-up and settle rounds: a stream as
``filestore-stream.py`` sends one, ONE packet long, to a path of the rounds'
own (``<prefix>r<k>``).  A round of one such stream to every group moves
0.2 GB over the three replicas where whole files would move 3 GB, and after
the warm-up every replica's stream directory, every client's connection and
leader hint and every server's stream port have been used once."""

import os

from benchmarks.harness.generator import load_op

_STREAM = load_op(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "filestore-stream")


def sender(client, traffic: dict):
    return _STREAM.stream_sender(client, traffic, "r",
                                 int(traffic["stream"]["packet_bytes"]))
