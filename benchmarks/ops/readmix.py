"""The operation ``readmix``: the counter example's two requests in the
traffic's ``read_share``.  A group's k-th request of this sender is a ``GET``
by ``io().send_read_only`` (a linearizable read: no log entry, no fsync;
``CounterClient`` ends with one) or the traffic's payload (``INCREMENT``) by
the ordered ``io().send``, decided by
``random.Random("readmix:<group index>:<k>").random() < read_share``: by the
group's place in the deployment and not by its id, which comes from the
seed, so that every ``--seed`` sends the same reads and writes to the same
places of the same groups' sequences, in another order.  What was sent goes
to the generator as ``GET`` or as the payload; the reply of either is the
count as ASCII digits."""

import random

GET = "GET"


def sender(client, traffic: dict):
    share = float(traffic["read_share"])
    text = traffic["payload_ascii"]
    payload, index = text.encode("ascii"), traffic["group_index"]
    api = client.io()
    sent = 0

    def send():
        nonlocal sent
        k, sent = sent, sent + 1
        if random.Random(f"readmix:{index}:{k}").random() < share:
            return GET, api.send_read_only(b"GET")
        return text, api.send(payload)
    return send
