"""Seconds per solve: from the window's opening to the last solve's
result, over the solves completed (a closed loop of one user)."""
from readers import delivered


def read(record):
    done = delivered(record)
    if not done:
        return None
    return (max(j["t_recv"] for j in done) - record["window"]["wall0"]) \
        / len(done)
