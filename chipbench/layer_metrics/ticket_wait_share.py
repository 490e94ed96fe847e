"""Mean over the transfer lanes of the % of the window inside
`datapipe.ticket_wait`: a lane waiting for a prefetch ticket, which is the
pipe waiting for the consumer. High when the pipe keeps up."""

from chipbench import spans


def read(obs):
    by = spans.shares(obs, "datapipe.ticket_wait")
    return sum(by.values()) / len(by) if by else None
