"""Run one benchmark cell and print counters of its server's ``/metrics``.

``benchmark/run.py`` (run here unedited, in this process) compares two
counter families and prints no other; an issue that asks "which path did the
compiled programs take" wants the counters that are counted once a trace
(``pa_qk_prologue_total``, ``pa_attention_route_total``,
``pa_upsample_conv_total``, ``pa_video_decode_total``, …) or of the loader's
residency rule (``pa_model_residency_total``, ``pa_params_resident_bytes``).
This wrapper
listens to the ``/metrics`` texts the harness fetches and, when the run ends,
prints the samples of the named families from the LAST one (the end of the
measured window) on standard error; the run's own lines are untouched:

    python scripts/cell_counters.py pa_qk_prologue_total,pa_attention_route_total \\
        --workload sd35m-b1-1024.closed --seed 7 --seconds 45 --trace 1
"""

from __future__ import annotations

import os
import runpy
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    families = set(sys.argv[1].split(","))
    sys.path.insert(0, os.path.join(_REPO, "benchmark"))
    from yardstick import client

    texts: list[str] = []
    totals = client.metric_totals

    def listening(text: str):
        texts.append(text)
        return totals(text)

    client.metric_totals = listening
    sys.argv = [os.path.join(_REPO, "benchmark", "run.py"), *sys.argv[2:]]
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    finally:
        for line in (texts[-1].splitlines() if texts else []):
            if line.split("{", 1)[0].split(" ", 1)[0] in families:
                print(line, file=sys.stderr)


if __name__ == "__main__":
    main()
