"""A named attr of the program's spans, per statement: the sum of `attr`
over the spans called `names` (every span where `names` is not given) in
the statement's trace on the system's side (the server's, where the
statement was served). Reduced over the window's statements of the given
`kinds` (all kinds where not given; with `after`, only statements that
directly follow one of those kinds, as `span_self_ms` has it) as their
median, or with `stat` "sum" as their sum over the window; times `scale`.

Only statements with a span that carries the attr count. A program that
records the attr sets it on every traced span of its kind, 0 where
nothing happened (`upload_bytes` on every `bind`, `xla_compiles` on
every dispatch), so the reading is a number in every traced run, 0
included; where no span carries it, as on a program from before the attr
existed, there is nothing to read: None."""

import spans


def read(ctx, attr, names=None, kinds=None, after=None, stat="median",
         scale=1):
    per_statement, prev = [], None
    for r in ctx["statements"]:
        follows, prev = prev, r["kind"]
        if (kinds is not None and r["kind"] not in kinds) or not r["ok"]:
            continue
        if after is not None and follows not in after:
            continue
        for tr in r.get("traces", ()):
            if tr["kind"] != ctx["back"]:
                continue
            found = [sp["attrs"][attr] for sp in spans.walk(tr["root"])
                     if (names is None or sp["name"] in names)
                     and attr in sp.get("attrs", {})]
            if found:
                per_statement.append(sum(found) * scale)
    if stat == "sum":
        return sum(per_statement) if per_statement else None
    return spans.median(per_statement)
