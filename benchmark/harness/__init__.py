"""The benchmark's yardstick: loader, decks, window loop, job runners,
counters, trace reduction, cost counts and peaks. Nothing here is edited to
add a cell: configurations, traffic mixes and layer metrics are files."""
