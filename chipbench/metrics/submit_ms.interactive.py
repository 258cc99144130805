"""submit_ms.interactive: mean host ms inside ``QuantumScheduler.submit``
per interactive request submitted in the window (the benchmark's
``submit`` span: the request's graph and samples, planning and plan
verification)."""


def read(record):
    took = [e - s for name, s, e in record["spans"]
            if name == "submit" and s < record["seconds"]]
    return 1000.0 * sum(took) / len(took) if took else None
