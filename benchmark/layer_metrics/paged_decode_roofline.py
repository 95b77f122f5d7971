"""flash_decode_paged's share of its HBM roofline (percent). Source: device trace and the adapter's bytes per context token."""
from benchmark.readers import paged_decode_roofline as read  # noqa: F401
