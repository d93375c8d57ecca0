"""Batched LM serving with continuous batching (weight-stationary deployment).

Thin front-end over repro.launch.serve's SlotServer — submits a mixed batch of
requests with different prompt/output lengths and reports throughput + latency
percentiles.

    PYTHONPATH=src python examples/serve_lm.py --arch mixtral-8x22b --requests 8
"""
import argparse

from repro.launch.serve import main as serve_main

if __name__ == '__main__':
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default='qwen3-14b')
    ap.add_argument('--requests', type=int, default=8)
    ap.add_argument('--slots', type=int, default=4)
    ap.add_argument('--max-new', type=int, default=8)
    a = ap.parse_args()
    serve_main(['--arch', a.arch, '--smoke', '--requests', str(a.requests),
                '--slots', str(a.slots), '--max-new', str(a.max_new)])
