select
    l_suppkey as supplier_no,
    sum(l_extendedprice * (1 - l_discount)) as total_revenue
from
    lineitem
where
    l_shipdate >= date '{date_lo}'
    and l_shipdate < date '{date_hi}'
group by
    l_suppkey
order by
    supplier_no
