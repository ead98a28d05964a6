"""Of the program's spans named `span` that ended inside the window and
carry every tag of `where`, the share in % that carry no tag `unset`
(or an empty one).

params: span    the span's name ("repair:block")
        where   {tag: value} a span has to carry to be counted
        unset   the tag whose absence is the good outcome

Nothing where no such span ended in the window: a program that does not
tag its spans so gives none, never an error.
"""

from benchmarks.harness import span_tags


def read(params: dict, run) -> float | None:
    spans = [tags for tags in span_tags.ended(params["span"], run.t0, run.t1)
             if all(tags.get(k) == v for k, v in params["where"].items())]
    if not spans:
        return None
    kept = sum(not tags.get(params["unset"]) for tags in spans)
    return 100.0 * kept / len(spans)
