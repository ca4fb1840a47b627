"""client_MiBps (host clock): client bytes of every op issued in the
window and acknowledged, over the time from the window's start until the
last of those ops completed.  All the window's work over all its time:
ops in flight at the close are drained and count, with their time, so
the closed loop's batches of completions do not step the rate."""


def read(w):
    if not w.ops:
        return None
    done = sum(o.nbytes for o in w.ops if o.ok)
    t_last = max(o.t1 for o in w.ops)
    return done / (t_last - w.t_start) / (1 << 20)
