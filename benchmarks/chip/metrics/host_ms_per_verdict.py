"""Host (the mapper's layers around the device walk): each request's
``service.map`` span, from the program's span recorder
(``repro.core.spans``, on from the window's open to its drain in a traced
run), less the union of that request's ``walk.segment`` spans inside it;
averaged over the requests, in ms."""


def read(ctx):
    roots = [s for s in ctx.spans if s.name == "service.map"]
    if not roots:
        return None
    segments = {}
    for s in ctx.spans:
        if s.name == "walk.segment":
            segments.setdefault(s.request, []).append((s.start, s.end))
    host = 0.0
    for r in roots:
        covered, cursor = 0.0, r.start
        for a, b in sorted(segments.get(r.request, [])):
            a, b = max(a, cursor), min(b, r.end)
            if b > a:
                covered += b - a
                cursor = b
        host += (r.end - r.start) - covered
    return 1e3 * host / len(roots)
