"""Median get latency: scheduled send to the returning flush."""

from bench.traffic import percentile


def read(ctx):
    lat = [done - sent for _, sent, done, ok, _ in ctx.window.gets]
    return 1e3 * percentile(lat, 50) if lat else None
