"""Subprocess consumer for ``tests/test_transport.py``'s two-process kill.

Runs in its OWN process: connects a :class:`~repro.core.transport.RemoteBus`
to a served bus, joins a queue group (optionally keyed) and consumes with
``auto_ack=False`` — each message's ``"k,i"`` record is written + flushed to
``--outfile`` BEFORE the ack frame is sent, the same effect-then-acknowledge
discipline that makes redelivery after a crash exactly-once end-to-end: a
message is either (a) unwritten and unacked — redelivered to a survivor — or
(b) written and acked exactly once.

``--kill-after N`` simulates a consumer crash: after N acked messages the
process dies via ``os._exit`` (no unsubscribe, no socket shutdown — the
server notices via EOF/heartbeat and re-homes the member's backlog).  The
kernel flushes the TCP send buffer before FIN, so every ack sent before the
exit reaches the server — which is what makes the kill test deterministic:
the acked set and the written set are identical.

Usage (spawned through ``tests/transport_harness.spawn_worker``):

    python tests/workers/transport_worker.py --addr 127.0.0.1:47000 \
        --subject ticks --group pool [--key k] --name w1 \
        --outfile /tmp/w1.log [--kill-after 200]
"""
from __future__ import annotations

import argparse
import os
import sys

BATCH = 32          # max messages pulled (and acked) per loop
IDLE_EXIT_S = 30.0  # clean exit after this many idle seconds


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--addr", required=True, help="host:port of the BusServer")
    ap.add_argument("--subject", required=True)
    ap.add_argument("--group", required=True)
    ap.add_argument("--key", default=None,
                    help="payload field for keyed delivery (else plain group)")
    ap.add_argument("--name", required=True,
                    help="stable member name (the keyed ring identity)")
    ap.add_argument("--outfile", required=True,
                    help="records land here as 'k,i' lines, one per message")
    ap.add_argument("--kill-after", type=int, default=None,
                    help="os._exit after this many acked messages (crash sim)")
    args = ap.parse_args()

    from repro.core.delivery import Group, Keyed
    from repro.core.transport import RemoteBus
    import time

    bus = RemoteBus(args.addr, peer=args.name, connect_timeout=10.0)
    token = bus.issue_token(args.name, [args.subject])
    policy = Keyed(args.group, args.key) if args.key else Group(args.group)
    sub = bus.subscribe(args.subject, token=token, policy=policy,
                        name=args.name, auto_ack=False)
    consumed = 0
    last_msg = time.monotonic()
    # block-buffered: the explicit flush + fsync before each ack is the
    # effect-then-acknowledge barrier
    with open(args.outfile, "a") as out:
        while True:
            msgs = sub.next_batch(BATCH, timeout=0.2)
            if not msgs:
                if sub.closed:
                    return 3  # connection dropped / subject closed
                if time.monotonic() - last_msg > IDLE_EXIT_S:
                    bus.close()
                    return 0
                continue
            last_msg = time.monotonic()
            for m in msgs:
                out.write(f"{m.payload['k']},{m.payload['i']}\n")
            out.flush()
            os.fsync(out.fileno())
            sub.ack(len(msgs))          # effect recorded -> acknowledge
            consumed += len(msgs)
            if args.kill_after is not None and consumed >= args.kill_after:
                os._exit(42)            # crash: no goodbye, no unsubscribe


if __name__ == "__main__":
    sys.exit(main())
