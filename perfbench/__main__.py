"""``python -m perfbench``: one run of one cell in one new process."""
import time

_T_PROCESS = time.monotonic()  # before any heavy import: setup_s starts here

import sys  # noqa: E402

from perfbench.run import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_process=_T_PROCESS))
