"""The operation ``write``: the traffic's payload as a write through the
group's log, by the client's ordered blocking API; answered once the entry
is committed and applied.  An operation is a file of its own, found by the
traffic file's ``op``: ``sender(client, traffic)`` makes of one group's
``RaftClient`` a ``send()`` that gives what it sends, as ASCII, and the
awaitable of the reply."""


def sender(client, traffic: dict):
    text = traffic["payload_ascii"]
    payload = text.encode("ascii")
    api = client.io()
    return lambda: (text, api.send(payload))
