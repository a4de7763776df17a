from repro.serve.admission import AdmissionPolicy
from repro.serve.fleet import FleetEngine, FleetWorker
from repro.serve.scheduler import CoalescingScheduler, Ticket

__all__ = ["AdmissionPolicy", "FleetEngine", "FleetWorker",
           "CoalescingScheduler", "Ticket"]
