"""Output tokens of the requests completed inside the window, over the
window.  Under a backlog this is the server's capacity."""


def read(run):
    return sum(r.req.out_len for r in run.done_in_window()) / run.seconds
