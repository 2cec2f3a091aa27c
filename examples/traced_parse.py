"""Traced parsing: where does a parse spend its time?

    PYTHONPATH=src python examples/traced_parse.py [--smoke]

A parse's time goes to the service queue, the host's preparation of the
padded chunk grid, the fused device program, the copy of its packed
columns back to the host, the host's assembly of the forest, and the
speculation-width report.  The paper's cost model splits the device part
further by phase — chunk reach, the associative join, build&merge.  This
example turns on the observability layer (ROADMAP "Observability") and
shows both splits through the supported surface:

  * ``ParserConfig(obs=ObsConfig(enabled=True, span_log=...))`` — tracing
    on, spans mirrored to a JSONL file;
  * a direct ``parse`` (with tracing on it runs the phase-split programs:
    reach / join / build&merge / host build spans) and a ``submit`` →
    ticket round trip (the fused route: queue wait, batch compute over
    host prep / device / fetch / assembly), both with a speculation span
    and a ``trace_id`` on the result;
  * the span trees, validated and pretty-printed from the JSONL log;
  * ``Parser.stats()`` as a metrics view: cataloged counters/gauges (padded
    and text cells per bucket, device-to-host bytes) and the per-bucket
    queue/compute p50/p99 split;
  * the Prometheus rendering of the same registry.
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parents[1] / "src"))

import repro
from repro.obs import prometheus_text, read_spans_jsonl, validate_span_tree


def print_tree(spans, trace_id):
    tree = validate_span_tree(spans, trace_id)
    root = tree["root"]
    print(f"  trace {trace_id}  root={root['name']}  "
          f"{root['duration_s'] * 1e3:8.2f} ms  attrs={root['attrs']}")

    def walk(parent, depth):
        kids = [s for s in tree["children"] if s["parent_id"] == parent["span_id"]]
        for c in sorted(kids, key=lambda s: s["t_start_s"]):
            print(f"    {'   ' * depth}└─ {c['name']:<24s} "
                  f"{c['duration_s'] * 1e3:8.2f} ms")
            walk(c, depth + 1)

    walk(root, 0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run (default sizes already are)")
    ap.parse_args()

    span_log = Path("spans.jsonl")
    span_log.unlink(missing_ok=True)

    cfg = repro.ParserConfig(
        regex="(a|b|ab)+",
        n_chunks=4,
        obs=repro.ObsConfig(enabled=True, span_log=str(span_log)),
    )
    with repro.Parser(cfg) as parser:
        # direct route: phase-split spans around each jitted program
        direct = parser.parse("abab" * 64)
        print(f"parse ok={direct.ok} backend={direct.backend} "
              f"bucket={direct.bucket} trace_id={direct.trace_id}")

        # service route: queue wait, then the fused program's batch
        tickets = [parser.submit("ab" * n) for n in (8, 16, 24)]
        served = [t.result() for t in tickets]
        print(f"served {len(served)} tickets "
              f"(trace_ids {[r.trace_id for r in served]})")

        spans = read_spans_jsonl(span_log)
        print(f"\nspan log: {len(spans)} spans in {span_log}")
        print("\ndirect route (phase attribution):")
        print_tree(spans, direct.trace_id)
        print("\nticket route (queue wait, then the fused program):")
        print_tree(spans, served[0].trace_id)

        stats = parser.stats()
        print("\nper-bucket latency split (queue wait vs device compute):")
        for bucket, d in stats["parse"]["buckets"].items():
            print(f"  bucket {bucket}: served={d['served']} "
                  f"p99_queue={d['p99_queue_s'] * 1e3:.2f} ms "
                  f"p99_compute={d['p99_compute_s'] * 1e3:.2f} ms")

        print("\ncells the fused program ran, per bucket (text / padding):")
        metrics = stats["metrics"]
        for text, pad in zip(metrics["text_cells_total"],
                             metrics["padded_cells_total"]):
            print(f"  bucket {text['labels']['bucket']}: "
                  f"{text['value']:.0f} text, {pad['value']:.0f} padding")

        print("\nprometheus exposition (first 12 lines):")
        for line in prometheus_text(stats["metrics"]).splitlines()[:12]:
            print(f"  {line}")

    span_log.unlink(missing_ok=True)   # keep example runs tidy


if __name__ == "__main__":
    main()
