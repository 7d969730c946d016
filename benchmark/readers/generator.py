from benchmark.timing import percentile


def lateness_p99_ms(run):
    late = [(r["sent"] - r["due"]) * 1e3 for r in run.records
            if "sent" in r]
    return percentile(late, 99) if late else None
