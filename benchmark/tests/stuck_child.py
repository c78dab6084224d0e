"""A server child that never serves: it takes ``serve_child``'s arguments,
binds nothing and sleeps, so ``/status`` is never answered.  No JAX.  Used
by test_processes only."""

import time

if __name__ == "__main__":
    while True:
        time.sleep(3600)
