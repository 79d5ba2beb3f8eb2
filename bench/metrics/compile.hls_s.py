"""Seconds of `hls.compile` in set-up (host clock): trace, passes and
schedule, or a design-cache hit."""


def read(view):
    return view.setup_parts.get("hls_compile")
