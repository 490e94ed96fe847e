"""% of the top_k x tokens routing choices of the first sparse layer that
fell on the experts this chip holds (`RowsHeld` over top_k x tokens,
median of the window's steps): the rows its grouped products ran over.
12.5 (32 of 256) if routing is even."""


def read(obs):
    share = obs.get("held_rows_share")
    return 100.0 * share if share is not None else None
