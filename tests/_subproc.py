"""Run a JAX snippet in a subprocess with a forced host device count.

Multi-device tests must not set XLA_FLAGS in this process (smoke tests and
benches must see 1 device), so each distributed test spawns a fresh interpreter.
"""
import pathlib
import subprocess
import sys

from repro.launch.mesh import host_device_env

REPO = pathlib.Path(__file__).resolve().parent.parent
SRC = str(REPO / 'src')


def run_with_devices(snippet: str, n_devices: int, timeout: int = 600) -> str:
    proc = subprocess.run([sys.executable, '-c', snippet],
                          env=host_device_env(n_devices),
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f'subprocess failed\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}')
    return proc.stdout
