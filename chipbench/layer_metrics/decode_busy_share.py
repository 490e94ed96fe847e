"""Mean over the decode workers of the % of a worker's time inside
`datapipe.decode` (its own stamps of the decode function). A worker's
spans reach the recorder with its acks, which the parent reads when a lane
asks for the next chunk: seconds after the work, with a deep ring. So the
share is taken over the time the snapshot accounts for on that worker's
lane (`idle + decode + ring_put`, which tile it), not over the window."""

from chipbench import spans


def read(obs):
    got = spans.taken(obs)
    if got is None:
        return None
    busy, whole = {}, {}
    for s in got[0]:
        if s["name"] in ("datapipe.idle", "datapipe.decode",
                         "datapipe.ring_put"):
            w, d = s["attrs"]["worker"], s["t1"] - s["t0"]
            whole[w] = whole.get(w, 0.0) + d
            if s["name"] == "datapipe.decode":
                busy[w] = busy.get(w, 0.0) + d
    shares = [100.0 * busy.get(w, 0.0) / t for w, t in whole.items() if t > 0]
    return sum(shares) / len(shares) if shares else None
