"""Serve executable: one node process of the socket-transport cluster.

  python -m accord_tpu.serve --node-id 1 --listen 127.0.0.1:7101 \
      --peers 1=127.0.0.1:7101,2=127.0.0.1:7102,3=127.0.0.1:7103

tests/test_serve.py spawns three of these and drives them with
serve/loadgen.py.
"""
from __future__ import annotations

import sys

from accord_tpu.serve.server import main

if __name__ == "__main__":
    sys.exit(main())
