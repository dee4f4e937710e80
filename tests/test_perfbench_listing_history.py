"""The history of the benchmark's listing, where the driver counts it:
``perfbench/tests/test_listing.py``'s walk of the two recorded listings (a
case a row: nothing either reported is lost but what was retired by name),
loaded by path (that directory is no package) and re-exported. JSON only."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "perfbench", "tests", "test_listing.py")
_spec = importlib.util.spec_from_file_location("perfbench_test_listing",
                                               _PATH)
_listing = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_listing)

test_nothing_a_listing_reported_is_lost_but_what_was_retired_by_name = \
    _listing.test_nothing_a_listing_reported_is_lost_but_what_was_retired_by_name
